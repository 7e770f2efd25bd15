"""Continuous-batching inference engine with AOT-warmed bucketed shapes.

The engine decouples request arrival from device stepping:

  * requests land in a :class:`~timm_tpu.serve.queueing.RequestQueue`; the
    scheduler thread admits runs of up to the largest declared bucket —
    full buckets immediately, partial buckets when the oldest request's
    deadline expires (no request starves waiting for batch-mates);
  * every (model, bucket) program is **AOT-compiled at startup** via
    ``jax.jit(...).lower().compile()``. With the persistent compile cache
    (PR 4) warm, a restart re-loads executables from disk instead of
    recompiling — restart-to-ready is disk-bound, not compile-bound. The
    per-model prewarm records JAX's cache hit/miss events so a deployment
    can assert "zero fresh compiles" after the first boot;
  * dispatch is **double-buffered**: ``jax.device_put`` uploads batch N+1
    (asynchronously, into a donated input buffer) while the device still
    runs batch N; the scheduler only blocks on a result once
    ``transfer_depth`` steps are in flight — the DevicePrefetcher pattern
    from PR 4 applied to the request path;
  * **no shape outside the declared bucket set ever reaches the compiler**:
    runs are padded to the smallest fitting bucket and executed through the
    precompiled AOT executables, which reject any other shape; the engine
    additionally asserts the bucket is declared before every dispatch;
  * multiple models stay resident through an HBM-budgeted LRU
    :class:`~timm_tpu.serve.residency.ModelPool`; ``block_scan`` defaults ON
    (for serving, the O(1)-in-depth startup-latency win dominates and the
    re-stack HBM cost doesn't — PERF.md).

CPU-runnable end to end: the load drill (serve/drill.py ``canonical_drill``)
exercises all of the above as a tier-1 smoke (tests/test_serve.py).
"""
from __future__ import annotations

import logging
import threading
import time
from collections import Counter, deque
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from ..utils.compile_cache import cache_event_total as _event_total
from ..utils.compile_cache import collect_cache_events
from .bucketing import DEFAULT_BUCKETS, pad_rows, select_bucket, strip_rows, validate_buckets
from .queueing import RequestQueue, ServeFuture
from .residency import ModelPool, ResidentModel

_logger = logging.getLogger(__name__)

__all__ = ['InferenceEngine', 'collect_cache_events']


class _Inflight:
    __slots__ = ('out', 'requests', 'bucket', 'dispatched_at')

    def __init__(self, out, requests, bucket, dispatched_at):
        self.out = out
        self.requests = requests
        self.bucket = bucket
        self.dispatched_at = dispatched_at


class InferenceEngine:
    """See module docstring. Typical use::

        engine = InferenceEngine(buckets=(1, 4, 16, 64), max_wait_ms=5.0)
        engine.add_model('vit_base_patch16_224', checkpoint='best.npz')
        engine.start()
        future = engine.submit(image)           # (H, W, C) float32, normalized
        logits = future.result(timeout=1.0)     # (num_classes,) float32
        engine.shutdown(drain=True)

    The engine serves ONE mesh (default: a single device — one serving
    replica per process). Pass an explicit ``('data','fsdp'[, 'model'])``
    mesh to shard weights/batches over multiple chips; every bucket must
    then be divisible by ``mesh.size`` (validated at construction).
    """

    def __init__(
            self,
            buckets: Sequence[int] = DEFAULT_BUCKETS,
            max_wait_ms: float = 10.0,
            mesh=None,
            transfer_depth: int = 2,
            hbm_budget_bytes: Optional[int] = None,
            block_scan: bool = True,
            input_dtype=None,
            max_pending: int = 10_000,
            configure_cache: bool = True,
            persist_all_programs: bool = False,
    ):
        import jax
        import jax.numpy as jnp

        from ..parallel import create_mesh, data_sharding
        from ..utils import configure_compile_cache

        if configure_cache:
            # serving startup wants every bucket program on disk: restart-to-
            # ready must be disk-bound. persist_all_programs drops the
            # min-compile-time threshold so even sub-second bucket programs
            # (small models / small buckets) persist.
            configure_compile_cache(**({'min_compile_time_secs': 0.0} if persist_all_programs else {}))
        self.mesh = mesh if mesh is not None else create_mesh(devices=jax.devices()[:1])
        self._n_batch_shards = int(self.mesh.size)
        self.buckets = validate_buckets(buckets, divisor=self._n_batch_shards)
        self.max_wait_s = float(max_wait_ms) / 1e3
        self.transfer_depth = max(1, int(transfer_depth))
        self.block_scan = block_scan
        self.input_dtype = input_dtype or jnp.float32
        self._data_sharding = data_sharding(self.mesh, ndim=4)
        self._queue = RequestQueue(max_bucket=self.buckets[-1],
                                   max_wait_s=self.max_wait_s,
                                   max_pending=max_pending)
        self.pool = ModelPool(self.mesh, budget_bytes=hbm_budget_bytes,
                              prewarm_fn=self._prewarm)
        # executables survive weight eviction: an AOT program holds code, not
        # parameters, so re-admitting an evicted model costs a factory build +
        # device_put, never a recompile. Bounded by models x buckets.
        self._exec_cache: Dict[Tuple[str, int], object] = {}
        self._inflight: 'deque[_Inflight]' = deque()
        self._thread: Optional[threading.Thread] = None
        self._started = False
        self.stats: Dict = {
            'submitted': 0, 'completed': 0, 'failed': 0, 'steps': 0,
            'padded_slots': 0, 'steps_by_bucket': Counter(),
            'request_sizes': Counter(),   # dispatched-batch size histogram
            'prewarm': {}, 'max_inflight': 0,
        }

    # -- model registration / prewarm -----------------------------------------

    def add_model(self, name: str, factory=None, checkpoint: Optional[str] = None,
                  input_size: Optional[Tuple[int, int, int]] = None,
                  prewarm: bool = True, quantize: Optional[str] = None,
                  quantized_checkpoint: Optional[str] = None,
                  **model_kwargs) -> None:
        """Register ``name`` with the residency pool. ``factory`` overrides
        the default ``timm_tpu.create_model(name, **model_kwargs)`` (+
        optional verified checkpoint load). ``prewarm=True`` loads and
        AOT-compiles every bucket now; otherwise the first request pays it.
        ``quantize='int8'`` serves post-training weight-only int8: the LRU
        budget is charged the ~0.27x footprint and every bucket program
        compiles against the int8 pytree with dequant fused at use
        (``quantized_checkpoint`` loads saved qvalues/scales instead of
        re-quantizing the factory's weights)."""
        if factory is None:
            def factory():
                import timm_tpu
                model = timm_tpu.create_model(name, **model_kwargs)
                if checkpoint:
                    from ..models import load_checkpoint
                    load_checkpoint(model, checkpoint)
                return model
        if input_size is None and 'img_size' in model_kwargs:
            s = int(model_kwargs['img_size'])
            input_size = (s, s, 3)

        base_factory = factory

        def serving_factory():
            model = base_factory()
            if self.block_scan and hasattr(model, 'set_block_scan'):
                # startup latency dominates serving; scan keeps the per-bucket
                # trace/compile O(1) in depth (heterogeneous stacks fall back
                # to the loop inside the model, bit-identically)
                model.set_block_scan(True)
            model.eval()
            return model

        self.pool.register(name, serving_factory, input_size=input_size,
                           quantize=quantize,
                           quantized_checkpoint=quantized_checkpoint)
        if prewarm:
            self.pool.acquire(name)

    def _prewarm(self, res: ResidentModel) -> None:
        """AOT-compile every declared bucket for a freshly-loaded model,
        recording wall time and compile-cache hit/miss events."""
        t0 = time.perf_counter()
        exec_hits = 0
        with collect_cache_events() as events:
            for bucket in self.buckets:
                key = (res.name, bucket)
                exe = self._exec_cache.get(key)
                if exe is not None:
                    exec_hits += 1
                else:
                    exe = self._compile_bucket(res, bucket)
                    self._exec_cache[key] = exe
                res.compiled[bucket] = exe
        ms = (time.perf_counter() - t0) * 1e3
        stats = {
            'programs': len(self.buckets),
            'ms': round(ms, 1),
            'exec_cache_hits': exec_hits,
            'cache_hits': _event_total(events, 'cache_hits'),
            'fresh_compiles': _event_total(events, 'cache_misses'),
        }
        res.prewarm_stats.update(stats)
        self.stats['prewarm'][res.name] = stats
        _logger.info(
            f'serve prewarm {res.name}: {stats["programs"]} bucket programs in '
            f'{ms:.0f}ms ({stats["cache_hits"]} disk-cache hits, '
            f'{stats["fresh_compiles"]} fresh compiles)')

    def _bucket_jit(self, res: ResidentModel):
        """The ONE construction of a bucket program's jit: donation of the
        input batch buffer is declared here and only here, so both the prewarm
        compile path and `donation_report` observe the same program — a
        dropped `donate_argnums` is visible to the lint, not just to grep."""
        import jax
        import jax.numpy as jnp
        from flax import nnx

        graphdef = res.graphdef

        if res.quantize:
            from ..quantize import dequantize_tree

            def infer(state, x):
                # dequant INSIDE the program: the int8 qvalues/scales are the
                # program inputs (what HBM holds between steps); the dense
                # weights are fused transients of the matmul epilogue
                return nnx.merge(graphdef, dequantize_tree(state))(x).astype(jnp.float32)
        else:
            def infer(state, x):
                return nnx.merge(graphdef, state)(x).astype(jnp.float32)

        # donate the input buffer: each step uploads a fresh batch, XLA may
        # reuse it as scratch instead of holding both copies in HBM. When the
        # backend can't alias it (CPU, logits smaller than the image batch)
        # jax warns per-shape; that's the expected no-op case, not a bug.
        return jax.jit(infer, donate_argnums=(1,))

    def _bucket_in_spec(self, res: ResidentModel, bucket: int):
        import jax
        h, w, c = res.input_size
        return jax.ShapeDtypeStruct((bucket, h, w, c), self.input_dtype,
                                    sharding=self._data_sharding)

    def _compile_bucket(self, res: ResidentModel, bucket: int):
        import warnings
        x_spec = self._bucket_in_spec(res, bucket)
        with warnings.catch_warnings():
            warnings.filterwarnings('ignore', message='Some donated buffers were not usable')
            return self._bucket_jit(res).lower(res.state, x_spec).compile()

    def aot_executables(self, model: str) -> Dict[int, object]:
        """bucket -> compiled AOT executable for `model` (prewarmed or first-
        request-compiled so far). The perfbudget probe and the serve donation
        lint introspect these directly (`cost_analysis()`, HLO text)."""
        return {b: exe for (name, b), exe in self._exec_cache.items() if name == model}

    def donation_report(self, model: str) -> Dict[int, Dict]:
        """Per-bucket evidence that the input-batch donation actually reaches
        the compiled program, asserted via the lowering/executable rather than
        `donate_argnums` presence in source.

        Two observable outcomes, either of which proves the donor was
        declared and threaded through:
          * the compiled HLO header carries an ``input_output_alias`` entry
            (backend aliased the donated buffer — the TPU/live case);
          * lowering emitted jax's "Some donated buffers were not usable"
            warning (backend could not alias — the CPU/logits-smaller case;
            the warning is emitted ONLY for declared donors, so its presence
            is positive evidence the donation survived to lowering).
        If `donate_argnums` is removed from `_bucket_jit`, both signals
        disappear and `declared` goes False for every bucket."""
        import warnings
        res = self.pool.acquire(model)
        out: Dict[int, Dict] = {}
        for bucket in self.buckets:
            jitted = self._bucket_jit(res)
            with warnings.catch_warnings(record=True) as rec:
                warnings.simplefilter('always')
                lowered = jitted.lower(res.state, self._bucket_in_spec(res, bucket))
            unusable = any('donated buffers were not usable' in str(w.message) for w in rec)
            exe = self._exec_cache.get((model, bucket))
            if exe is None:
                exe = lowered.compile()
            header = exe.as_text().splitlines()[0] if hasattr(exe, 'as_text') else ''
            aliases = (header.count('may-alias') + header.count('must-alias')
                       if 'input_output_alias' in header else 0)
            out[bucket] = {
                'declared': bool(aliases or unusable),
                'aliases': int(aliases),
                'unusable_on_backend': bool(unusable),
            }
        return out

    # -- request path ---------------------------------------------------------

    def submit(self, image, model: Optional[str] = None) -> ServeFuture:
        """Enqueue one image; returns a future resolving to its logits row."""
        if not self._started:
            raise RuntimeError('InferenceEngine.submit before start(); call start() first')
        if model is None:
            registered = self.pool.registered
            if len(registered) != 1:
                raise ValueError(
                    f'model= is required when {len(registered)} models are registered '
                    f'({list(registered)})')
            model = registered[0]
        future = self._queue.submit(model, image)
        self.stats['submitted'] += 1
        return future

    # -- lifecycle ------------------------------------------------------------

    def start(self) -> 'InferenceEngine':
        if self._started:
            return self
        self._started = True
        self._thread = threading.Thread(target=self._loop, name='serve-scheduler',
                                        daemon=True)
        self._thread.start()
        return self

    def shutdown(self, drain: bool = True, timeout: float = 120.0) -> None:
        """Stop the engine. ``drain=True`` (the default) completes every
        pending and in-flight request first; ``drain=False`` fails pending
        requests and completes only the in-flight device steps."""
        if not self._started:
            return
        self._queue.close(drain=drain)
        if self._thread is not None:
            self._thread.join(timeout)
            if self._thread.is_alive():
                raise RuntimeError('serve scheduler failed to drain within '
                                   f'{timeout}s at shutdown')
            self._thread = None
        self._started = False
        advisory = self.bucket_advisory()
        if advisory:
            _logger.info(
                f'serve: bucket ladder {advisory["current"]} wasted '
                f'{advisory["current_waste"]:.1%} of computed rows over '
                f'{advisory["requests"]} dispatches; '
                f'autotune.propose_buckets suggests {advisory["proposed"]} '
                f'({advisory["proposed_waste"]:.1%} waste). Advisory only — '
                f'restart with buckets={tuple(advisory["proposed"])} to apply.')

    def __enter__(self) -> 'InferenceEngine':
        return self.start()

    def __exit__(self, *exc) -> None:
        self.shutdown(drain=not any(exc))

    # -- scheduler ------------------------------------------------------------

    def _loop(self) -> None:
        try:
            while True:
                # with steps in flight, poll briefly so result retirement
                # interleaves with admission; otherwise block until work,
                # a deadline, or shutdown
                timeout = 0.0005 if self._inflight else None
                admission = self._queue.wait_admission(timeout=timeout)
                if admission is None:
                    if self._inflight:
                        self._retire(self._inflight.popleft())
                        continue
                    if self._queue.finished():
                        break
                    continue
                self._dispatch(*admission)
                while len(self._inflight) >= self.transfer_depth:
                    self._retire(self._inflight.popleft())
        finally:
            while self._inflight:
                self._retire(self._inflight.popleft())

    def _dispatch(self, model_name: str, requests) -> None:
        import jax
        import jax.numpy as jnp

        try:
            res = self.pool.acquire(model_name)
            bucket = select_bucket(len(requests), self.buckets)
            x = np.stack([np.asarray(r.image) for r in requests])
            x, _valid = pad_rows(x, bucket)
            # hard guarantee: nothing outside the declared set reaches the
            # compiler — the AOT executables reject novel shapes, and this
            # assert catches a scheduling bug before the device does
            assert x.shape[0] in self.buckets, \
                f'batch shape {x.shape[0]} outside declared buckets {self.buckets}'
            # async upload (double-buffer): overlaps the running device step
            x_dev = jax.device_put(jnp.asarray(x, self.input_dtype), self._data_sharding)
            out = res.compiled[bucket](res.state, x_dev)
            self._inflight.append(_Inflight(out, requests, bucket, time.perf_counter()))
            self.stats['steps'] += 1
            self.stats['steps_by_bucket'][bucket] += 1
            self.stats['request_sizes'][len(requests)] += 1
            self.stats['padded_slots'] += bucket - len(requests)
            self.stats['max_inflight'] = max(self.stats['max_inflight'], len(self._inflight))
        except Exception as e:
            _logger.exception(f'serve dispatch failed for {model_name} '
                              f'x{len(requests)}: {e}')
            for r in requests:
                r.future._set_exception(e)
            self.stats['failed'] += len(requests)

    def _retire(self, item: _Inflight) -> None:
        try:
            logits = np.asarray(item.out)  # blocks until the device step lands
            logits = strip_rows(logits, len(item.requests))
            for i, r in enumerate(item.requests):
                r.future._set_result(logits[i])
            self.stats['completed'] += len(item.requests)
        except Exception as e:
            _logger.exception(f'serve step failed at retirement: {e}')
            for r in item.requests:
                r.future._set_exception(e)
            self.stats['failed'] += len(item.requests)

    # -- introspection --------------------------------------------------------

    def pending(self) -> int:
        return len(self._queue)

    def snapshot_stats(self) -> Dict:
        """Point-in-time copy of engine + pool counters (drill reporting)."""
        out = dict(self.stats)
        out['steps_by_bucket'] = dict(self.stats['steps_by_bucket'])
        out['request_sizes'] = dict(self.stats['request_sizes'])
        out['pool'] = dict(self.pool.stats)
        out['resident'] = list(self.pool.resident_names)
        return out

    def bucket_advisory(self, max_buckets: int = 5) -> Optional[Dict]:
        """Compare the declared bucket ladder against the optimal ladder for
        the dispatched-batch size histogram (`autotune.propose_buckets`).
        Returns None until traffic exists or when the declared ladder is
        already optimal; advisory only — ladders are compile-time surface."""
        hist = {s: c for s, c in self.stats['request_sizes'].items() if c > 0}
        if not hist:
            return None
        from ..autotune import ladder_waste, propose_buckets
        proposed = propose_buckets(hist, max_buckets=max(len(self.buckets),
                                                         max_buckets))
        current_waste = ladder_waste(self.buckets, hist)
        proposed_waste = ladder_waste(proposed, hist)
        if tuple(proposed) == tuple(sorted(self.buckets)) \
                or proposed_waste >= current_waste:
            return None
        return {'current': tuple(sorted(self.buckets)),
                'proposed': tuple(proposed),
                'current_waste': round(current_waste, 4),
                'proposed_waste': round(proposed_waste, 4),
                'requests': int(sum(hist.values()))}
