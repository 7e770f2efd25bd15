"""Fault-injection harness for resilience drills.

Spec grammar (comma-separated, via `train.py --fault-inject` or env
`TIMM_TPU_FAULT_INJECT`):

  truncate_ckpt     truncate the NEXT checkpoint write after commit (one-shot)
  nan_grads@N       poison the batch at global update N so loss/grads go NaN;
                    nan_grads@N:K poisons K consecutive updates (abort drills)
  sigterm@N         deliver SIGTERM to this process at global update N (one-shot)
  io_error%M        raise IOError on every M-th sample read (exercises the
                    reader retry/backoff + poison-skip budget — and, when an
                    async checkpoint writer is armed, its durable-write path)
  resize@N:D        elastic-resize drill: deliver SIGTERM at global update N
                    (one-shot, like sigterm@N); the restarting harness reads
                    `resize_devices` = D and relaunches with that forced
                    device count (`--elastic` resume rebuilds the mesh)
  kill_host@N[:P]   host-loss drill: SIGKILL process P (default 0) at global
                    update N — no recovery save, no clean exit, exactly what
                    a preempted/failed pod host looks like. Every process can
                    carry the same spec; only the one whose
                    `jax.process_index()` == P dies (single-process runs with
                    P=0 kill themselves)

The injector is deliberately dumb: hooks call `take`/`nan_at`/`sigterm_at`/
`io_error_tick` at the natural fault site, so the tests and manual drills
exercise the REAL recovery paths (durable fallback, non-finite sentinel,
preemption save, reader retry) rather than mocks.
"""
from __future__ import annotations

import logging
import os
import threading
from typing import Dict, Optional

_logger = logging.getLogger(__name__)

__all__ = ['FaultInjector', 'get_fault_injector', 'set_fault_injector', 'fault_selftest']

_KINDS_ONESHOT = ('truncate_ckpt',)
_KINDS_AT = ('nan_grads', 'sigterm', 'resize', 'kill_host')
_KINDS_EVERY = ('io_error',)


class FaultInjector:
    """Parsed fault spec with thread-safe trigger bookkeeping."""

    def __init__(self, spec: str = ''):
        self.spec = (spec or '').strip()
        self._lock = threading.Lock()
        self._oneshot: Dict[str, bool] = {}     # kind -> armed
        self._at: Dict[str, tuple] = {}         # kind -> (start_update, count)
        self._fired: Dict[str, bool] = {}
        self._every: Dict[str, int] = {}        # kind -> period M
        self._ticks: Dict[str, int] = {}
        self.resize_devices: Optional[int] = None
        self.kill_host_process: int = 0
        for part in filter(None, (p.strip() for p in self.spec.split(','))):
            if '@' in part:
                kind, _, n = part.partition('@')
                if kind not in _KINDS_AT:
                    raise ValueError(f'unknown @-fault {kind!r} in spec {spec!r}')
                n, _, suffix = n.partition(':')
                if kind == 'kill_host':
                    # kill_host@N:P — the :P suffix is the target process
                    # index (default 0), not a window; fires exactly once
                    if suffix and int(suffix) < 0:
                        raise ValueError(f'kill_host process index must be >= 0: {part!r}')
                    self.kill_host_process = int(suffix) if suffix else 0
                    self._at[kind] = (int(n), 1)
                elif kind == 'resize':
                    # resize@N:D — the :D suffix is the restart's forced
                    # device count, not a window; the fault fires exactly once
                    if not suffix or int(suffix) < 1:
                        raise ValueError(
                            f'resize fault needs a device count >= 1: {part!r} '
                            f'(want resize@N:D)')
                    self.resize_devices = int(suffix)
                    self._at[kind] = (int(n), 1)
                else:
                    self._at[kind] = (int(n), max(1, int(suffix)) if suffix else 1)
            elif '%' in part:
                kind, _, m = part.partition('%')
                if kind not in _KINDS_EVERY:
                    raise ValueError(f'unknown %-fault {kind!r} in spec {spec!r}')
                if int(m) < 1:
                    raise ValueError(f'fault period must be >= 1: {part!r}')
                self._every[kind] = int(m)
            elif part in _KINDS_ONESHOT:
                self._oneshot[part] = True
            else:
                raise ValueError(f'unknown fault {part!r} in spec {spec!r} '
                                 f'(known: {_KINDS_ONESHOT + _KINDS_AT + _KINDS_EVERY})')

    def __bool__(self):
        return bool(self._oneshot or self._at or self._every)

    def take(self, kind: str) -> bool:
        """Consume a one-shot fault; True exactly once if armed."""
        with self._lock:
            if self._oneshot.get(kind):
                self._oneshot[kind] = False
                return True
        return False

    def _at_window(self, kind: str, update_idx: int) -> bool:
        window = self._at.get(kind)
        return window is not None and window[0] <= update_idx < window[0] + window[1]

    def nan_at(self, update_idx: int) -> bool:
        return self._at_window('nan_grads', update_idx)

    def sigterm_at(self, update_idx: int) -> bool:
        with self._lock:
            if self._at_window('sigterm', update_idx) and not self._fired.get('sigterm'):
                self._fired['sigterm'] = True
                return True
        return False

    def resize_at(self, update_idx: int) -> bool:
        """True exactly once when `resize@N:D` is armed and update N is
        reached. The caller SIGTERMs itself (same recovery-save path as a
        real preemption); the restarting harness reads `resize_devices` for
        the forced device count of the relaunch."""
        with self._lock:
            if self._at_window('resize', update_idx) and not self._fired.get('resize'):
                self._fired['resize'] = True
                return True
        return False

    def kill_host_at(self, update_idx: int, process_index: int = 0) -> bool:
        """True exactly once when `kill_host@N[:P]` is armed, update N is
        reached, AND this is process P. The caller SIGKILLs itself — no
        recovery save, no consensus: the survivors must detect the loss via
        the KV-store consensus timeout and stop on their own."""
        if process_index != self.kill_host_process:
            return False
        with self._lock:
            if self._at_window('kill_host', update_idx) and not self._fired.get('kill_host'):
                self._fired['kill_host'] = True
                return True
        return False

    def io_error_tick(self) -> bool:
        """True on every M-th call when `io_error%M` is armed (thread-safe)."""
        period = self._every.get('io_error')
        if not period:
            return False
        with self._lock:
            self._ticks['io_error'] = self._ticks.get('io_error', 0) + 1
            return self._ticks['io_error'] % period == 0


_injector: Optional[FaultInjector] = None
_injector_lock = threading.Lock()


def get_fault_injector() -> Optional[FaultInjector]:
    """Process-wide injector; lazily built from TIMM_TPU_FAULT_INJECT. Returns
    None when no faults are armed (hooks stay zero-cost)."""
    global _injector
    if _injector is None:
        spec = os.environ.get('TIMM_TPU_FAULT_INJECT', '')
        if not spec.strip():
            return None
        with _injector_lock:
            if _injector is None:
                _injector = FaultInjector(spec)
    return _injector if _injector else None


def set_fault_injector(spec_or_injector) -> Optional[FaultInjector]:
    """Install (or clear, with ''/None) the process-wide injector."""
    global _injector
    with _injector_lock:
        if spec_or_injector is None or spec_or_injector == '':
            _injector = None
        elif isinstance(spec_or_injector, FaultInjector):
            _injector = spec_or_injector
        else:
            _injector = FaultInjector(str(spec_or_injector))
        if _injector:
            _logger.info(f'Fault injection armed: {_injector.spec}')
    return _injector


def fault_selftest(spec: str = '', tmp_dir: Optional[str] = None) -> dict:
    """Exercise every injection hook + its recovery path on CPU, no model.

    Used by tests/test_resilience.py so the harness itself is covered in tier-1 without slow runs. Returns
    {'ok': bool, 'checks': {name: bool}, 'spec': parsed-spec}.
    """
    import tempfile

    import numpy as np

    from . import durable
    from .retry import SkipBudget, TooManyBadSamples, retry_io

    if spec:
        FaultInjector(spec)  # parse check of the user-provided spec
    checks = {}
    prev = _injector
    work = tmp_dir or tempfile.mkdtemp(prefix='timm_tpu_faultdrill_')
    try:
        # 1. truncate_ckpt → verification fails → fallback finds the older valid file
        set_fault_injector('')
        good = os.path.join(work, 'checkpoint-0.npz')
        durable.atomic_write_npz(good, {'w': np.arange(8.0)}, meta={'epoch': 0})
        set_fault_injector('truncate_ckpt')
        bad = os.path.join(work, 'checkpoint-1.npz')
        durable.atomic_write_npz(bad, {'w': np.arange(8.0) + 1}, meta={'epoch': 1})
        ok_bad, _ = durable.verify_checkpoint(bad)
        _, _, used = durable.load_with_fallback(bad, search_dir=work)
        checks['truncate_then_fallback'] = (not ok_bad) and used == good
        # 2. io_error%2 → retry_io rides through transient faults
        set_fault_injector('io_error%2')
        injector = get_fault_injector()

        def read():
            if injector.io_error_tick():
                raise IOError('injected')
            return 42

        checks['io_retry'] = retry_io(read, retries=3, base_delay=0.0, desc='selftest') == 42
        # 3. poison-skip budget trips after the configured number of bad samples
        budget = SkipBudget(budget=2)
        budget.record(ValueError('poison'), 'sample 0')
        budget.record(ValueError('poison'), 'sample 1')
        try:
            budget.record(ValueError('poison'), 'sample 2')
            checks['skip_budget'] = False
        except TooManyBadSamples:
            checks['skip_budget'] = True
        # 4. @-faults: nan window covers [N, N+K), sigterm fires exactly once
        fi = FaultInjector('nan_grads@3:2,sigterm@5')
        checks['at_faults'] = (not fi.nan_at(2) and fi.nan_at(3) and fi.nan_at(4)
                               and not fi.nan_at(5)
                               and fi.sigterm_at(5) and not fi.sigterm_at(5))
        # 5. resize@N:D parses the forced device count and fires exactly once
        fi = FaultInjector('resize@4:2')
        checks['resize'] = (fi.resize_devices == 2 and not fi.resize_at(3)
                            and fi.resize_at(4) and not fi.resize_at(4))
        # 6. kill_host@N:P targets exactly process P, fires exactly once
        fi = FaultInjector('kill_host@6:1')
        checks['kill_host'] = (fi.kill_host_process == 1
                               and not fi.kill_host_at(6, process_index=0)
                               and not fi.kill_host_at(5, process_index=1)
                               and fi.kill_host_at(6, process_index=1)
                               and not fi.kill_host_at(6, process_index=1)
                               and FaultInjector('kill_host@2').kill_host_process == 0)
    finally:
        set_fault_injector(prev)
        if tmp_dir is None:
            import shutil
            shutil.rmtree(work, ignore_errors=True)
    return {'ok': all(checks.values()), 'checks': checks, 'spec': spec}
