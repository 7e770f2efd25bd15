"""Batch loader: decode in worker processes (image files) or threads (array
slices), ordered collation, prefetch (reference: timm/data/loader.py:30-504).

TPU-native redesign of the reference's DataLoader+PrefetchLoader pair:
  * image files are decoded and augmented in worker processes with their own
    interpreters (`decode_worker.py`; they never import JAX and end with
    their parent), which hand raw pixels back down pipes; a bounded queue gives
    pipelined prefetch — the main interpreter's lock is touched a few times a
    batch, not a few thousand (PERF.md section 6, PR 30)
  * per-host sharding for multi-process (pod) runs replaces the distributed
    sampler: each host reads its `jax.process_index()` slice
  * normalization happens on device inside the consuming step (mean/std are
    published as loader attributes), mirroring the reference's on-GPU
    normalize (loader.py:124-159)
  * RandomErasing applies post-collate on the host batch
"""
from __future__ import annotations

import fcntl
import logging
import os
import pickle
import queue
import subprocess
import sys
import threading
import time
import weakref
from typing import Callable, Optional

import numpy as np

from ..resilience import SkipBudget, TooManyBadSamples, get_fault_injector
from ..utils import tracing
from . import decode_worker
from .constants import IMAGENET_DEFAULT_MEAN, IMAGENET_DEFAULT_STD
from .random_erasing import RandomErasing
from .transforms_factory import create_transform

__all__ = ['create_loader', 'DecodeWorkerDied', 'DevicePrefetcher', 'StreamingLoader', 'ThreadedLoader']

_logger = logging.getLogger(__name__)

# marker a worker emits for a sample dropped against the poison budget, so the
# collator keeps its consumed-count bookkeeping without padding the batch
_SKIPPED = object()


def _skip_or_fatal(skip_budget: SkipBudget, exc: Exception, idx: int):
    """A poisoned sample against the epoch's budget: the skip marker, or, with
    the budget exhausted, the error that fails the epoch loudly."""
    try:
        skip_budget.record(exc, f'sample index {idx}')
        return _SKIPPED
    except TooManyBadSamples as fatal:
        return fatal


class StreamingLoader:
    """Batch loader over an ITERABLE dataset (wds/tfds streaming readers).

    The reader owns shard assignment (process x worker). During training with
    `num_workers > 1` and a worker-aware reader (set_worker_info), N producer
    threads each stream a worker-strided copy of the reader and decode/augment
    in parallel; otherwise a single producer thread prefetches ahead of the
    consumer. Either way a bounded queue overlaps input work with the device
    step. RandomErasing applies post-collate like ThreadedLoader. For
    multi-host runs with a known sample count, batch counts are EQUALIZED:
    every host emits exactly `len(self)` batches per epoch, cycling its
    stream if its shard slice runs short (the streaming analogue of the
    padded distributed sampler). Single-host streams naturally (short final
    batch on eval).
    """

    def __init__(
            self,
            dataset,
            batch_size: int,
            is_training: bool = False,
            drop_last: Optional[bool] = None,
            num_workers: int = 1,
            prefetch: int = 4,
            re_prob: float = 0.0,
            re_mode: str = 'const',
            re_count: int = 1,
            re_num_splits: int = 0,
            mean=IMAGENET_DEFAULT_MEAN,
            std=IMAGENET_DEFAULT_STD,
            process_index: int = 0,
            process_count: int = 1,
            seed: int = 42,
            **kwargs,
    ):
        self.dataset = dataset
        self.batch_size = batch_size
        self.is_training = is_training
        self.drop_last = is_training if drop_last is None else drop_last
        self.num_workers = max(1, num_workers)
        self.prefetch = prefetch
        self.epoch = 0
        self.mean = np.asarray(mean, np.float32)
        self.std = np.asarray(std, np.float32)
        self.random_erasing = RandomErasing(
            probability=re_prob, mode=re_mode, min_count=re_count,
            num_splits=re_num_splits, mean=self.mean, std=self.std,
            seed=seed) if re_prob > 0 and is_training else None
        self.process_index = process_index
        self.process_count = process_count

    def set_epoch(self, epoch: int):
        self.epoch = epoch
        if self.random_erasing is not None:
            self.random_erasing.set_epoch(epoch)  # resume-reproducible stream
        if hasattr(self.dataset, 'set_epoch'):
            self.dataset.set_epoch(epoch)

    def _num_batches(self) -> Optional[int]:
        try:
            n = len(self.dataset)
        except TypeError:
            return None
        per_host = n // self.process_count if self.process_count > 1 else n
        if self.drop_last:
            return max(per_host // self.batch_size, 1)
        return max(-(-per_host // self.batch_size), 1)

    def __len__(self):
        n = self._num_batches()
        if n is None:
            raise TypeError(
                'streaming dataset length unknown (no sample count); '
                'pass --epoch-size or provide an _info.json sidecar')
        return n

    def __iter__(self):
        if hasattr(self.dataset, 'set_epoch'):
            self.dataset.set_epoch(self.epoch)
        # single host: no lockstep requirement — stream naturally (short final
        # batch on eval, like ThreadedLoader). Multi-host: equalize counts.
        target_batches = self._num_batches() if self.process_count > 1 or self.drop_last else None

        stop = threading.Event()
        sample_q: 'queue.Queue' = queue.Queue(maxsize=self.prefetch * self.batch_size)

        def _worker_streams():
            """Worker-strided reader copies (or None when unsupported)."""
            reader = getattr(self.dataset, 'reader', None)
            if not (self.is_training and self.num_workers > 1 and reader is not None
                    and hasattr(reader, 'set_worker_info')):
                return None
            import copy
            transform = getattr(self.dataset, 'transform', None)
            target_transform = getattr(self.dataset, 'target_transform', None)

            def stream(worker_reader):
                for img, target in worker_reader:
                    if transform is not None:
                        img = transform(img)
                    if target_transform is not None:
                        target = target_transform(target)
                    yield img, target

            out = []
            for w in range(self.num_workers):
                r = copy.copy(reader)
                r.set_worker_info(w, self.num_workers)
                out.append(stream(r))
            return out

        needed = None if target_batches is None else target_batches * self.batch_size
        emitted_lock = threading.Lock()
        state = {'emitted': 0}

        def producer(stream):
            try:
                for sample in stream:
                    if stop.is_set():
                        return
                    with emitted_lock:
                        if needed is not None and state['emitted'] >= needed:
                            return
                        state['emitted'] += 1
                    sample_q.put(sample)
            except Exception as e:
                sample_q.put(e)

        def run_producers():
            # outer loop restarts the full stream set when the shard slice
            # ran short of the equalized count (multi-host lockstep)
            while True:
                streams = _worker_streams() or [iter(self.dataset)]
                threads = []
                for s in streams:
                    t = threading.Thread(target=producer, args=(s,), daemon=True)
                    t.start()
                    threads.append(t)
                for t in threads:
                    t.join()
                with emitted_lock:
                    done = (needed is None or state['emitted'] == 0
                            or state['emitted'] >= needed)
                if done or stop.is_set():
                    break
                if hasattr(self.dataset, 'set_epoch'):
                    self.dataset.set_epoch(self.epoch + 1000 + state['emitted'])
            sample_q.put(None)

        threading.Thread(target=run_producers, daemon=True).start()

        batch_imgs, batch_targets = [], []
        try:
            while True:
                item = sample_q.get()
                if item is None:
                    break
                if isinstance(item, Exception):
                    raise item
                img, target = item
                batch_imgs.append(img)
                batch_targets.append(target)
                if len(batch_imgs) == self.batch_size:
                    yield self._collate(batch_imgs, batch_targets)
                    batch_imgs, batch_targets = [], []
            if batch_imgs and not self.drop_last:
                yield self._collate(batch_imgs, batch_targets)
        finally:
            stop.set()
            try:
                while True:
                    sample_q.get_nowait()
            except queue.Empty:
                pass

    def _collate(self, imgs, targets):
        x, t = _collate_arrays(imgs, targets)
        if self.random_erasing is not None:
            x = self.random_erasing(x)
        return x, t



class DevicePrefetcher:
    """Double-buffer device-prefetch stage over any host-batch iterable.

    The host loaders above stop at numpy: the consuming step then pays a
    synchronous host→device transfer per batch (an input stall the device
    sits idle through). This wrapper keeps up to ``size`` upcoming batches in
    flight on device — ``jax.device_put`` dispatches the transfer
    asynchronously, so batch k+1 streams to HBM while the step runs on batch
    k. Batches are sharded over the global mesh batch axis via
    ``parallel.shard_batch`` (single-device meshes degrade to a plain
    device_put); re-sharding the yielded arrays downstream is a no-op.

    Drain/stop semantics (PR-3 preemption contract): early termination of the
    consumer (preemption checkpoint, exception, ``break``) closes the inner
    iterator through the generator's ``finally`` — worker threads observe
    their stop event and exit, and prefetched-but-unyielded device batches
    are simply dropped. The recovery checkpoint records the index of the last
    *yielded* batch, so ``--resume auto`` skip-counting is unaffected by the
    prefetch depth.

    Attribute access (``len()``, ``sampler``, ``mean``/``std``,
    ``set_epoch``…) delegates to the wrapped loader.
    """

    def __init__(self, loader, size: int = 2):
        self.loader = loader
        self.size = max(1, int(size))

    def __getattr__(self, name):
        return getattr(self.loader, name)

    def __len__(self):
        return len(self.loader)

    def __iter__(self):
        import collections

        from ..parallel import shard_batch

        buf = collections.deque()
        it = iter(self.loader)

        def put_next():
            batch = next(it)
            with tracing.span('loader.h2d'):
                buf.append(shard_batch(batch))

        try:
            while len(buf) < self.size:
                try:
                    put_next()
                except StopIteration:
                    break
            while buf:
                out = buf.popleft()
                try:
                    put_next()
                except StopIteration:
                    pass
                yield out
        finally:
            buf.clear()
            close = getattr(it, 'close', None)
            if close is not None:
                close()


_CHUNK = 8   # samples a decode thread or process hands over at once


def _collate_arrays(imgs, targets):
    """Stack a list of samples; AugMix tuple samples (clean, aug1..augN) are
    concatenated split-major along batch with targets repeated per split
    (reference loader.py fast_collate tuple path)."""
    if isinstance(imgs[0], (tuple, list)):
        n_splits = len(imgs[0])
        x = np.concatenate([np.stack([im[j] for im in imgs]) for j in range(n_splits)])
        t = np.tile(np.asarray(targets), n_splits)
        return x, t
    return np.stack(imgs), np.asarray(targets)


class DecodeWorkerDied(RuntimeError):
    """A decode process ended before the loader closed it: the epoch fails, as
    with an exhausted skip budget; nothing restarts the worker silently."""


class _DecodePool:
    """`num_workers` decode processes (`decode_worker.py`), each with a copy of
    the dataset, kept across epochs. An epoch's chunks of indices are dealt round
    robin and read back in the same order, so the samples arrive in index order
    whatever each worker's pace. A worker can be one chunk ahead in its pipe
    and one in its hands; then its write blocks until the reader comes round."""

    def __init__(self, dataset, num_workers: int):
        self.dataset = dataset
        self.num_workers = num_workers
        self.procs: list = []
        self.results: list = []           # unbuffered read ends of the workers' result pipes
        self.started_at: Optional[float] = None         # perf_counter, until the first batch has been reported
        self._reader: Optional[threading.Thread] = None
        self._stop: Optional[threading.Event] = None    # of the pass the reader serves

    def start(self):
        """Start the workers if they are not running (a no-op between epochs)."""
        if self.procs:
            return
        self.started_at = time.perf_counter()
        injector = get_fault_injector()
        # the dataset is pickled once, and travels as bytes inside each worker's first message
        init = dict(dataset=pickle.dumps(self.dataset, pickle.HIGHEST_PROTOCOL),
                    fault_spec=injector.spec if injector else '')
        try:
            for worker in range(self.num_workers):
                read_fd, write_fd = os.pipe()
                self.results.append(os.fdopen(read_fd, 'rb', buffering=0))
                try:
                    fcntl.fcntl(read_fd, fcntl.F_SETPIPE_SZ, 1 << 20)   # a chunk of 8 x 224 x 224 x 3 in few writes
                except OSError:
                    pass                                                  # the default size works, in more of them
                try:
                    self.procs.append(subprocess.Popen([sys.executable, decode_worker.__file__, str(write_fd)],
                                                       stdin=subprocess.PIPE, pass_fds=(write_fd,)))
                finally:
                    os.close(write_fd)      # the worker's end: when it dies the reader sees end of file
            for worker in range(self.num_workers):
                self._send(worker, dict(init, worker=worker))
        except BaseException:
            self.close()
            raise

    def _send(self, worker: int, message):
        try:
            pickle.dump(message, self.procs[worker].stdin, pickle.HIGHEST_PROTOCOL)
            self.procs[worker].stdin.flush()
        except BrokenPipeError:
            raise self._died(worker) from None

    def _died(self, worker: int) -> DecodeWorkerDied:
        tracing.count('loader.worker_exits')
        proc = self.procs[worker]
        try:
            code = proc.wait(timeout=2)
        except subprocess.TimeoutExpired:
            code = 'still running'
        return DecodeWorkerDied(f'decode worker {worker} (pid {proc.pid}) ended before the loader closed it '
                                f'(exit code {code}): the epoch cannot be completed')

    def alive(self) -> int:
        return sum(proc.poll() is None for proc in self.procs)

    def run_epoch(self, seed: int, epoch: int, chunks: list, deliver: Callable, stop: threading.Event):
        """Give every worker its chunks of the epoch and start the thread that
        reads their results in chunk order: `deliver(idxs, head, pixels)` for each
        (False stops it), `deliver(None, error, None)` when a worker has died."""
        self._stop = stop
        for worker in range(self.num_workers):
            self._send(worker, (seed, epoch, chunks[worker::self.num_workers]))

        def read():
            for c, idxs in enumerate(chunks):
                frame = decode_worker.read_frame(self.results[c % self.num_workers])
                if stop.is_set():
                    return
                if frame is None:
                    deliver(None, self._died(c % self.num_workers), None)
                    return
                if not deliver(idxs, *frame):
                    return

        self._reader = threading.Thread(target=read, daemon=True)
        self._reader.start()

    def close(self):
        """End the workers (each leaves when its stdin closes; one that does not
        within 2 s is killed), then the reader they fed and with it the pass it
        served, then free the pipes."""
        if self._stop is not None:
            self._stop.set()
        for proc in self.procs:
            try:
                proc.stdin.close()
            except BrokenPipeError:
                pass
        for proc in self.procs:
            try:
                proc.wait(timeout=2)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        if self._reader is not None:
            self._reader.join()
            self._reader = self._stop = None
        for result in self.results:
            result.close()
        self.procs, self.results = [], []


class ThreadedLoader:
    """Batches of a map-style dataset: index sharding, epoch shuffle, ordered
    collation for evaluation, the poison-skip budget and `prefetch` batches of
    back-pressure, over one of two decode stages. Which one falls on what the
    dataset says its items are: decoded files (`dataset.decodes_files`: open, JPEG
    decode, PIL augmentation, milliseconds of C sections that release and retake
    the interpreter lock some 25 times a sample) are decoded in worker PROCESSES
    that stay for the loader's life; array slices (`TokenWindows`: microseconds,
    no lock traffic, 128 KB an item to ship) in THREADS that live for one epoch.
    `close()` ends the processes; so does the end of this process, however it ends.
    """

    def __init__(
            self,
            dataset,
            batch_size: int,
            is_training: bool = False,
            num_workers: int = 4,
            drop_last: Optional[bool] = None,
            shuffle: Optional[bool] = None,
            seed: int = 42,
            num_aug_repeats: int = 0,
            prefetch: int = 4,
            re_prob: float = 0.0,
            re_mode: str = 'const',
            re_count: int = 1,
            re_num_splits: int = 0,
            mean=IMAGENET_DEFAULT_MEAN,
            std=IMAGENET_DEFAULT_STD,
            process_index: int = 0,
            process_count: int = 1,
    ):
        self.dataset = dataset
        self.batch_size = batch_size
        self.is_training = is_training
        self.num_workers = max(1, num_workers)
        self.drop_last = is_training if drop_last is None else drop_last
        self.shuffle = is_training if shuffle is None else shuffle
        self.seed = seed
        self.epoch = 0
        self.prefetch = prefetch
        self.mean = np.asarray(mean, np.float32)
        self.std = np.asarray(std, np.float32)
        self.random_erasing = RandomErasing(
            probability=re_prob, mode=re_mode, min_count=re_count,
            num_splits=re_num_splits, mean=self.mean, std=self.std,
            seed=seed) if re_prob > 0 and is_training else None
        self.process_index = process_index
        self.process_count = process_count
        self.num_aug_repeats = num_aug_repeats if is_training else 0
        self._pool = None
        if getattr(dataset, 'decodes_files', False):
            self._pool = _DecodePool(dataset, self.num_workers)
            weakref.finalize(self, self._pool.close)    # a loader dropped without close() keeps no process
        self._halt: Optional[Callable] = None    # ends the pass in progress

        self._local_indices = self._shard_indices(shuffled=False)

    def start(self):
        """Start the decode processes ahead of the first `iter()` (which starts
        them otherwise), so that their imports overlap the caller's own set-up."""
        if self._pool is not None:
            self._pool.start()

    def close(self):
        """End the pass in progress, if any, and the decode processes. Idempotent;
        a later `iter()` starts new ones."""
        if self._halt is not None:
            self._halt()
        if self._pool is not None:
            self._pool.close()
            tracing.gauge('loader.decode_procs', 0)

    def _repeat_aug_indices(self, rng) -> np.ndarray:
        """Repeated-augmentation sampling (reference distributed_sampler.py:54
        RepeatAugSampler): each sample appears `num_repeats` times adjacent in
        the shuffled order, replicas take interleaved slices (so each replica
        sees a DIFFERENT augmentation of the same image), and each replica
        truncates to ~len(dataset)/replicas samples per epoch."""
        import math
        n = len(self.dataset)
        reps = self.num_aug_repeats
        world = max(1, self.process_count)
        indices = np.arange(n)
        if self.shuffle:
            rng.shuffle(indices)
        indices = np.repeat(indices, reps)
        num_samples = int(math.ceil(n * reps / world))
        total = num_samples * world
        indices = np.concatenate([indices, indices[:total - len(indices)]])
        local = indices[self.process_index::world]
        # selected_round=256, selected_ratio=world (reference defaults)
        num_selected = int(math.floor(n // 256 * 256 / world)) if n >= 256 \
            else int(math.ceil(n / world))
        return local[:num_selected]

    def _shard_indices(self, shuffled: bool):
        rng = np.random.RandomState(self.seed + self.epoch)
        if self.num_aug_repeats:
            return self._repeat_aug_indices(rng)
        n = len(self.dataset)
        indices = np.arange(n)
        if shuffled:
            rng.shuffle(indices)
        if self.process_count > 1:
            # pad to equal per-host length (reference OrderedDistributedSampler)
            per_host = -(-n // self.process_count)
            padded = np.concatenate([indices, indices[:per_host * self.process_count - n]])
            indices = padded[self.process_index::self.process_count]
        return indices

    def set_epoch(self, epoch: int):
        self.epoch = epoch
        if self.random_erasing is not None:
            self.random_erasing.set_epoch(epoch)  # resume-reproducible stream

    def __len__(self):
        n = len(self._local_indices)
        if self.drop_last:
            return n // self.batch_size
        return -(-n // self.batch_size)

    def _decode_in_threads(self, used, hand_over: Callable, skip_budget: SkipBudget, stop: threading.Event):
        """The decode stage for items that are array slices: `num_workers`
        threads of this interpreter, each over a stride of the epoch's indices."""
        def worker(worker_indices):
            chunk = []
            for idx in worker_indices:
                if stop.is_set():
                    return
                # counters only on these threads: a span per sample would cost
                # the interpreter lock the main thread needs
                with tracing.busy('loader.decode_busy_ns'):
                    try:
                        sample = decode_worker.read_sample(self.dataset, int(idx))
                    except Exception as e:
                        sample = _skip_or_fatal(skip_budget, e, int(idx))
                tracing.count('loader.samples')
                chunk.append((int(idx), sample))
                if len(chunk) == _CHUNK:
                    if not hand_over(chunk):
                        return
                    chunk = []
            if chunk:
                hand_over(chunk)

        for w in range(self.num_workers):
            threading.Thread(target=worker, args=(used[w::self.num_workers],), daemon=True).start()

    def _decode_in_processes(self, used, hand_over: Callable, skip_budget: SkipBudget, stop: threading.Event):
        """The decode stage for items that are decoded files: the pool's workers
        decode chunks of the epoch's indices; here, on the pool's reader thread,
        their reports are added to the main process's counters, their poisoned
        samples put to the skip budget, and their pixels handed on as views."""
        def deliver(idxs, head, pixels) -> bool:
            if idxs is None:
                return hand_over([(-1, head)])      # a worker died: `head` is the error
            tracing.count('loader.samples', len(idxs))
            tracing.count('loader.decode_busy_ns', head['busy_ns'])
            bad = dict(head['bad'])
            good = iter(zip(pixels if pixels is not None else (), head['targets']))
            chunk = []
            for pos, idx in enumerate(idxs):
                if pos in bad:
                    chunk.append((idx, _skip_or_fatal(skip_budget, bad[pos], idx)))
                else:
                    img, target = next(good)
                    chunk.append((idx, (tuple(img) if head['splits'] else img, target)))
            return hand_over(chunk)

        used = [int(i) for i in used]
        chunks = [used[i:i + _CHUNK] for i in range(0, len(used), _CHUNK)]
        self._pool.run_epoch(self.seed, self.epoch, chunks, deliver, stop)

    def __iter__(self):
        if self._halt is not None:
            self._halt()    # the pool serves one pass at a time
        indices = self._shard_indices(shuffled=self.shuffle)
        num_batches = len(indices) // self.batch_size if self.drop_last \
            else -(-len(indices) // self.batch_size)

        # samples travel in chunks: every hand-over wakes the collator and costs both
        # threads the interpreter lock (PERF.md section 6, PR 25); the bound stays
        # `prefetch` batches of samples
        sample_q: 'queue.Queue' = queue.Queue(maxsize=max(1, self.prefetch * self.batch_size // _CHUNK))
        batch_q: 'queue.Queue' = queue.Queue(maxsize=self.prefetch)
        stop = threading.Event()

        def _put(q, item) -> bool:
            # put that stays responsive to shutdown (early-terminated iteration)
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        used = indices[:num_batches * self.batch_size] if self.drop_last else indices

        # training batches collate in arrival order (indices are already a
        # fresh shuffle, and this keeps sample_q backpressure intact); eval
        # restores deterministic index order so results are reproducible.
        # repeat-aug emits DUPLICATE indices, which the ordered path's
        # pending-by-index bookkeeping cannot represent — always unordered.
        ordered = not self.shuffle and not self.num_aug_repeats

        def collator():
            pending = {}
            order = list(used)
            pos = 0
            consumed = 0
            batch_imgs, batch_targets = [], []

            def emit(force_last: bool):
                nonlocal batch_imgs, batch_targets
                if len(batch_imgs) == self.batch_size or (force_last and batch_imgs and not self.drop_last):
                    x, t = _collate_arrays(batch_imgs, batch_targets)
                    if self.random_erasing is not None:
                        x = self.random_erasing(x)
                    ok = _put(batch_q, (x, t))
                    tracing.count('loader.batches')
                    batch_imgs, batch_targets = [], []
                    return ok
                return True

            try:
                while consumed < len(order) and not stop.is_set():
                    try:
                        chunk = sample_q.get(timeout=0.1)
                    except queue.Empty:
                        continue
                    for idx, sample in chunk:
                        consumed += 1
                        if isinstance(sample, Exception):
                            raise sample
                        if ordered:
                            pending[idx] = sample
                            while pos < len(order) and int(order[pos]) in pending:
                                s = pending.pop(int(order[pos]))
                                pos += 1
                                if s is not _SKIPPED:
                                    img, target = s
                                    batch_imgs.append(img)
                                    batch_targets.append(target)
                                if not emit(force_last=pos == len(order)):
                                    return
                        else:
                            if sample is not _SKIPPED:
                                img, target = sample
                                batch_imgs.append(img)
                                batch_targets.append(target)
                            if not emit(force_last=consumed == len(order)):
                                return
            except Exception as e:
                _put(batch_q, e)
            finally:
                _put(batch_q, None)

        exhausted = False

        def halt():
            """Leave nothing blocked: the threads see `stop`; decode processes in
            the middle of an epoch are ended, not drained (a later pass starts
            new ones); after a whole epoch they stay for the next."""
            self._halt = None
            stop.set()
            if self._pool is not None and not exhausted:
                self._pool.close()
            try:
                while True:
                    batch_q.get_nowait()
            except queue.Empty:
                pass
            if not exhausted:   # for whoever resumes this pass after a close() or a newer pass ended it
                batch_q.put_nowait(RuntimeError('this pass over the loader was ended before its epoch was'))

        self._halt = halt
        try:
            self.start()
            decode = self._decode_in_threads if self._pool is None else self._decode_in_processes
            decode(used, lambda chunk: _put(sample_q, chunk), SkipBudget(), stop)
            threading.Thread(target=collator, daemon=True).start()
            while True:
                tracing.gauge('loader.batch_q_depth', batch_q.qsize())
                if self._pool is not None:
                    tracing.gauge('loader.decode_procs', self._pool.alive())
                with tracing.span('loader.batch_wait'):
                    item = batch_q.get()
                if item is None:
                    exhausted = True
                    break
                if isinstance(item, Exception):
                    raise item
                if self._pool is not None and self._pool.started_at is not None:
                    _logger.info(f'loader: {self._pool.alive()} decode processes, first batch '
                                 f'{time.perf_counter() - self._pool.started_at:.2f} s after their start')
                    self._pool.started_at = None
                yield item
        finally:
            if self._halt is halt:
                halt()

    @property
    def sampler(self):
        return self  # set_epoch lives here; parity shim


def create_loader(
        dataset,
        input_size,
        batch_size: int,
        is_training: bool = False,
        no_aug: bool = False,
        re_prob: float = 0.0,
        re_mode: str = 'const',
        re_count: int = 1,
        re_split: bool = False,
        train_crop_mode=None,
        scale=None,
        ratio=None,
        hflip: float = 0.5,
        vflip: float = 0.0,
        color_jitter: float = 0.4,
        color_jitter_prob=None,
        grayscale_prob: float = 0.0,
        gaussian_blur_prob: float = 0.0,
        auto_augment=None,
        num_aug_repeats: int = 0,
        num_aug_splits: int = 0,
        interpolation: str = 'bilinear',
        mean=IMAGENET_DEFAULT_MEAN,
        std=IMAGENET_DEFAULT_STD,
        num_workers: int = 4,
        distributed: bool = False,
        crop_pct: Optional[float] = None,
        crop_mode: Optional[str] = None,
        crop_border_pixels: Optional[int] = None,
        collate_fn=None,
        fp16: bool = False,
        drop_last: Optional[bool] = None,
        seed: int = 42,
        persistent_workers: bool = True,
        worker_seeding: str = 'all',
        device_prefetch: int = 0,
        device_augment: bool = False,
        mixup=None,
        **kwargs,
):
    """(reference loader.py:205). Returns a ThreadedLoader yielding
    (images NHWC float32 [0,1], targets int) numpy batches.

    ``device_prefetch=N`` (default 0 = off) appends a DevicePrefetcher stage
    that keeps up to N batches in flight on device (sharded over the global
    mesh), overlapping host→device transfer with the running step. Leave off
    when the consumer still mutates batches on host (mixup, grad-accum
    concatenation).

    ``device_augment=True`` moves RandomErasing, Mixup/CutMix (pass the Mixup
    sampler via ``mixup=``) and normalize off the host: batches collate as
    raw uint8, the host samples only the augmentation *parameters*, and one
    donated jitted program per batch shape does the float math on device
    (data/device_augment.py). The loader then yields (input, target) device
    arrays — soft targets when mixup is active."""
    import jax

    if num_aug_repeats and not hasattr(dataset, '__getitem__'):
        raise ValueError('--aug-repeats requires a map-style (indexable) dataset')
    if device_augment:
        from .mixup import FastCollateMixup
        if isinstance(collate_fn, FastCollateMixup) or isinstance(mixup, FastCollateMixup):
            raise ValueError(
                'device_augment=True already applies mixup on device; a host-side '
                'FastCollateMixup collate would double-apply it. Pass a plain '
                'Mixup instance via mixup= (parameter sampling only) instead.')
        if not is_training:
            raise ValueError('device_augment=True is a train-path stage '
                             '(eval batches are not augmented)')
    if collate_fn is not None:
        raise NotImplementedError('custom collate_fn is not supported by ThreadedLoader')

    re_num_splits = 0
    if re_split:
        re_num_splits = num_aug_splits or 2

    # create_loader owns the dataset transform (reference loader.py:205 does
    # the same — the pipeline is derived from loader args)
    dataset.transform = create_transform(
        input_size,
        is_training=is_training,
        no_aug=no_aug,
        train_crop_mode=train_crop_mode,
        scale=scale,
        ratio=ratio,
        hflip=hflip,
        vflip=vflip,
        color_jitter=color_jitter,
        color_jitter_prob=color_jitter_prob,
        grayscale_prob=grayscale_prob,
        gaussian_blur_prob=gaussian_blur_prob,
        auto_augment=auto_augment,
        interpolation=interpolation,
        mean=mean,
        std=std,
        crop_pct=crop_pct,
        crop_mode=crop_mode,
        crop_border_pixels=crop_border_pixels,
        re_prob=0.0,  # RE applied post-collate by the loader
        separate=num_aug_splits > 0,
        output_dtype=np.uint8 if device_augment else None,
    )

    loader_kwargs = dict(
        batch_size=batch_size,
        is_training=is_training,
        drop_last=drop_last,
        # device_augment: host collates raw uint8 and samples erase params
        # only — the DeviceAugmentStage below owns erase application
        re_prob=0.0 if device_augment else re_prob,
        re_mode=re_mode,
        re_count=re_count,
        re_num_splits=re_num_splits,
        mean=mean,
        std=std,
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        seed=seed,
    )
    if not hasattr(dataset, '__getitem__'):
        # iterable (streaming) dataset: the reader owns shard assignment
        loader = StreamingLoader(dataset, num_workers=num_workers, **loader_kwargs)
    else:
        loader = ThreadedLoader(
            dataset,
            num_workers=num_workers,
            num_aug_repeats=num_aug_repeats,
            **loader_kwargs,
        )
    if device_prefetch:
        loader = DevicePrefetcher(loader, size=device_prefetch)
    if device_augment:
        from .device_augment import DeviceAugmentStage
        import jax.numpy as jnp
        re_sampler = RandomErasing(
            probability=re_prob, mode=re_mode, min_count=re_count,
            num_splits=re_num_splits, mean=np.asarray(mean, np.float32),
            std=np.asarray(std, np.float32), seed=seed) if re_prob > 0 else None
        loader = DeviceAugmentStage(
            loader, mean=mean, std=std, mixup=mixup, random_erasing=re_sampler,
            re_mode=re_mode, noise_seed=seed,
            out_dtype=jnp.float16 if fp16 else jnp.float32)
    return loader
