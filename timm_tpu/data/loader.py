"""Batch loader with threaded decode + prefetch
(reference: timm/data/loader.py:30-504).

TPU-native redesign of the reference's DataLoader+PrefetchLoader pair:
  * worker threads decode/augment (PIL releases the GIL in libjpeg), a
    bounded queue gives pipelined prefetch — replaces torch worker procs
  * per-host sharding for multi-process (pod) runs replaces the distributed
    sampler: each host reads its `jax.process_index()` slice
  * normalization happens on device inside the consuming step (mean/std are
    published as loader attributes), mirroring the reference's on-GPU
    normalize (loader.py:124-159)
  * RandomErasing applies post-collate on the host batch
"""
from __future__ import annotations

import queue
import random
import threading
from typing import Callable, Optional, Tuple

import numpy as np

from ..resilience import SkipBudget, TooManyBadSamples, get_fault_injector, retry_io
from ..utils import tracing
from .constants import IMAGENET_DEFAULT_MEAN, IMAGENET_DEFAULT_STD
from .random_erasing import RandomErasing
from .transforms_factory import create_transform

__all__ = ['create_loader', 'DevicePrefetcher', 'StreamingLoader', 'ThreadedLoader']

# marker a worker emits for a sample dropped against the poison budget, so the
# collator keeps its consumed-count bookkeeping without padding the batch
_SKIPPED = object()


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


_CHUNK = 8   # samples a decode thread hands the collator at once


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


class ThreadedLoader:
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

        self._local_indices = self._shard_indices(shuffled=False)

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

    def __iter__(self):
        indices = self._shard_indices(shuffled=self.shuffle)
        num_batches = len(indices) // self.batch_size if self.drop_last \
            else -(-len(indices) // self.batch_size)

        # samples travel in chunks: every hand-over wakes the collator and costs both
        # threads the interpreter lock, which the decode threads are short of
        # (PERF.md section 6, PR 25); the bound stays `prefetch` batches of samples
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

        skip_budget = SkipBudget()

        def _read(idx):
            injector = get_fault_injector()
            if injector is not None and injector.io_error_tick():
                raise IOError(f'[fault-inject] sample read {idx}')
            return self.dataset[int(idx)]

        def worker(worker_indices):
            chunk = []
            for idx in worker_indices:
                if stop.is_set():
                    return
                # counters only on these threads: a span per sample would cost
                # the interpreter they are suspected of starving the main thread of
                with tracing.busy('loader.decode_busy_ns'):
                    try:
                        # transient I/O faults (OSError) ride through jittered
                        # exponential backoff; anything still failing is poison
                        sample = retry_io(lambda: _read(idx), retries=3, base_delay=0.05,
                                          desc=f'sample {int(idx)}')
                    except Exception as e:
                        try:
                            skip_budget.record(e, f'sample index {int(idx)}')
                            sample = _SKIPPED
                        except TooManyBadSamples as fatal:
                            sample = fatal  # budget exhausted: fail the epoch loudly
                tracing.count('loader.samples')
                chunk.append((int(idx), sample))
                if len(chunk) == _CHUNK:
                    if not _put(sample_q, chunk):
                        return
                    chunk = []
            if chunk:
                _put(sample_q, chunk)

        used = indices[:num_batches * self.batch_size] if self.drop_last else indices
        workers = []
        for w in range(self.num_workers):
            t = threading.Thread(target=worker, args=(used[w::self.num_workers],), daemon=True)
            t.start()
            workers.append(t)

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

        ct = threading.Thread(target=collator, daemon=True)
        ct.start()

        try:
            while True:
                tracing.gauge('loader.batch_q_depth', batch_q.qsize())
                with tracing.span('loader.batch_wait'):
                    item = batch_q.get()
                if item is None:
                    break
                if isinstance(item, Exception):
                    raise item
                yield item
        finally:
            stop.set()
            # drain so blocked threads can observe stop and exit
            try:
                while True:
                    batch_q.get_nowait()
            except queue.Empty:
                pass

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
