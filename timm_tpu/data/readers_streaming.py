"""Streaming / sharded dataset readers
(reference: timm/data/readers/reader_wds.py, reader_tfds.py,
reader_image_in_tar.py).

Three readers for ImageNet-scale multi-host input:

  * ReaderImageInTar — map-style index over image members of tar file(s);
    labels from the member's parent directory name.
  * ReaderWds — iterable webdataset-style shard reader implemented directly
    on `tarfile` (no webdataset dependency): samples are members grouped by
    basename key, image from .jpg/.jpeg/.png/.webp, target from .cls/.json.
  * ReaderTfds — tensorflow_datasets wrapper (gated on the library being
    installed; this image ships without it, so construction raises with
    guidance — the sharding logic is exercised via ReaderWds which shares it).

Shard assignment follows the reference's InputContext scheme
(reader_tfds.py:207-249): the shard list is dealt round-robin over
`global_worker_id = dist_rank * num_workers + worker_id`. When there are
fewer shards than global workers, workers instead interleave SAMPLES within
their round-robin shard subset (even-split fallback).
"""
from __future__ import annotations

import glob
import io
import json
import logging
import os
import random
import tarfile
from typing import Callable, List, Optional, Tuple

from ..resilience import SkipBudget, get_fault_injector, retry_io

_logger = logging.getLogger(__name__)

__all__ = ['ReaderImageInTar', 'ReaderWds', 'ReaderTfds', 'assign_shards', 'expand_shard_pattern']

IMG_EXTENSIONS = ('.jpg', '.jpeg', '.png', '.webp', '.bmp')


def assign_shards(shards: List, global_worker_id: int, global_num_workers: int) -> List:
    """Round-robin shard assignment (reference InputContext semantics).
    Returns the subset of `shards` owned by this worker. When there are fewer
    shards than workers, multiple workers share a shard (caller interleaves
    samples via `sample_stride`)."""
    if global_num_workers <= 1:
        return list(shards)
    if len(shards) >= global_num_workers:
        return list(shards[global_worker_id::global_num_workers])
    # fewer shards than workers: worker w reads shard w % num_shards and
    # interleaves samples with the other workers mapped to the same shard
    return [shards[global_worker_id % len(shards)]]


def expand_shard_pattern(pattern: str) -> List[str]:
    """Expand `{000..012}` brace ranges and glob wildcards into a shard list."""
    import re
    m = re.search(r'\{(\d+)\.\.(\d+)\}', pattern)
    if m:
        lo, hi = m.group(1), m.group(2)
        width = len(lo)
        out = []
        for i in range(int(lo), int(hi) + 1):
            out.extend(expand_shard_pattern(pattern[:m.start()] + str(i).zfill(width) + pattern[m.end():]))
        return out
    if any(c in pattern for c in '*?['):
        return sorted(glob.glob(pattern))
    if os.path.isdir(pattern):
        return sorted(
            os.path.join(pattern, f) for f in os.listdir(pattern) if f.endswith('.tar'))
    return [pattern]


def _decode_image(data: bytes, input_img_mode: str = 'RGB'):
    from PIL import Image
    img = Image.open(io.BytesIO(data))
    img.load()
    if input_img_mode and img.mode != input_img_mode:
        img = img.convert(input_img_mode)
    return img


class ReaderImageInTar:
    """Map-style reader over images inside tar file(s)
    (reference reader_image_in_tar.py:191). Class labels come from each
    member's first path component (`<class>/<name>.jpg`)."""

    def __init__(self, root: str, class_map='', input_img_mode: str = 'RGB'):
        self.input_img_mode = input_img_mode
        tars = expand_shard_pattern(root)
        assert tars, f'no tar files found at {root}'
        self.samples: List[Tuple[str, str, str]] = []  # (tar_path, member_name, class_name)
        class_names = set()
        for tp in tars:
            with tarfile.open(tp) as tf:
                for m in tf.getmembers():
                    if not m.isfile():
                        continue
                    ext = os.path.splitext(m.name)[1].lower()
                    if ext not in IMG_EXTENSIONS:
                        continue
                    cls = m.name.split('/')[0] if '/' in m.name else ''
                    class_names.add(cls)
                    self.samples.append((tp, m.name, cls))
        self.samples.sort(key=lambda s: (s[0], s[1]))
        if class_map:
            from .readers import load_class_map
            self.class_to_idx = load_class_map(class_map)
        else:
            self.class_to_idx = {c: i for i, c in enumerate(sorted(class_names))}
        # tarfile seeks a shared file object; keep one handle PER THREAD so
        # ThreadedLoader workers don't interleave reads
        import threading
        self._tls = threading.local()

    def __getstate__(self):
        # open handles stay behind: a decode process opens its own
        import threading
        return dict(self.__dict__, _tls=threading.local())

    def _tar(self, path):
        cache = getattr(self._tls, 'tars', None)
        if cache is None:
            cache = self._tls.tars = {}
        tf = cache.get(path)
        if tf is None:
            tf = cache[path] = tarfile.open(path)
        return tf

    def __len__(self):
        return len(self.samples)

    def __getitem__(self, index: int):
        # returns (file-like, target) matching the ImageDataset reader contract
        tp, name, cls = self.samples[index]
        data = self._tar(tp).extractfile(name).read()
        return io.BytesIO(data), self.class_to_idx.get(cls, -1)

    def filename(self, index, basename=False, absolute=False):
        name = self.samples[index][1]
        return os.path.basename(name) if basename else name

    def filenames(self, basename=False, absolute=False):
        return [self.filename(i, basename) for i in range(len(self.samples))]


class ReaderWds:
    """Iterable webdataset-shard reader (reference reader_wds.py:262),
    implemented directly on `tarfile`.

    Each epoch: shards are (optionally) shuffled with a common seed, dealt to
    `dist_rank * num_workers + worker_id` round-robin, then streamed with a
    sample shuffle buffer. With fewer shards than workers, co-assigned
    workers interleave samples by stride.
    """

    def __init__(
            self,
            root: str,
            split: str = 'train',
            is_training: bool = False,
            batch_size: Optional[int] = None,
            seed: int = 42,
            shuffle_size: int = 2048,
            input_img_mode: str = 'RGB',
            input_key: Optional[str] = None,
            target_key: Optional[str] = None,
            dist_rank: int = 0,
            dist_num_replicas: int = 1,
    ):
        self.shards = expand_shard_pattern(root)
        assert self.shards, f'no shards found at {root}'
        self.is_training = is_training
        self.seed = seed
        self.shuffle_size = shuffle_size if is_training else 0
        self.input_img_mode = input_img_mode
        self.input_key = input_key
        self.target_key = target_key
        self.dist_rank = dist_rank
        self.dist_num_replicas = dist_num_replicas
        self.num_workers = 1
        self.worker_id = 0
        self.epoch = -1
        # sample count estimate: read a sidecar _info.json if present
        info_path = os.path.join(os.path.dirname(self.shards[0]), '_info.json')
        self.num_samples = None
        if os.path.exists(info_path):
            try:
                with open(info_path) as f:
                    self.num_samples = int(json.load(f).get('num_samples'))
            except (OSError, ValueError, TypeError) as e:
                _logger.warning(
                    f'Ignoring unreadable shard sidecar {info_path} ({e!r}); '
                    f'the loader length will be unknown — pass --epoch-size')

    def set_epoch(self, epoch: int):
        self.epoch = epoch

    def set_worker_info(self, worker_id: int, num_workers: int):
        self.worker_id = worker_id
        self.num_workers = max(1, num_workers)

    def __len__(self):
        if self.num_samples is None:
            raise TypeError('ReaderWds length unknown (no _info.json); use an explicit step count')
        return self.num_samples

    def _iter_shard(self, path):
        """Yield (key, {ext: bytes}) groups from one shard, in tar order.
        Shard open rides the transient-I/O retry policy (network filesystems
        drop tar opens far more often than member reads)."""
        cur_key, cur = None, {}
        with retry_io(lambda: tarfile.open(path), retries=3, base_delay=0.1,
                      retry_on=(OSError, tarfile.ReadError), desc=f'open shard {path}') as tf:
            for m in tf:
                if not m.isfile():
                    continue
                base, ext = os.path.splitext(m.name)
                ext = ext.lower().lstrip('.')
                if cur_key is not None and base != cur_key:
                    yield cur_key, cur
                    cur = {}
                cur_key = base
                cur[ext] = tf.extractfile(m).read()
            if cur_key is not None and cur:
                yield cur_key, cur

    def _decode(self, sample):
        img_data = None
        if self.input_key and self.input_key in sample:
            img_data = sample[self.input_key]
        else:
            for ext in ('jpg', 'jpeg', 'png', 'webp'):
                if ext in sample:
                    img_data = sample[ext]
                    break
        if img_data is None:
            return None
        img = _decode_image(img_data, self.input_img_mode)
        target = -1
        if self.target_key and self.target_key in sample:
            target = int(sample[self.target_key])
        elif 'cls' in sample:
            target = int(sample['cls'].decode())
        elif 'json' in sample:
            meta = json.loads(sample['json'])
            target = int(meta.get('label', meta.get('cls', -1)))
        return img, target

    def __iter__(self):
        global_num_workers = self.dist_num_replicas * self.num_workers
        global_worker_id = self.dist_rank * self.num_workers + self.worker_id
        shards = list(self.shards)
        rng = random.Random(self.seed + max(self.epoch, 0))
        if self.is_training:
            rng.shuffle(shards)  # common seed: all workers agree on the deal
        my_shards = assign_shards(shards, global_worker_id, global_num_workers)
        subshard = len(shards) < global_num_workers and global_num_workers > 1
        if subshard:
            # workers co-assigned to my shard are {w : w % S == gwid % S};
            # stride by that group's size so each sample lands on exactly one
            # worker even when S does not divide the worker count
            S = len(shards)
            group = global_worker_id % S
            stride = len(range(group, global_num_workers, S))
            offset = global_worker_id // S
        else:
            stride, offset = 1, 0

        buf = []
        i = -1
        skip_budget = SkipBudget()
        injector = get_fault_injector()
        for shard in my_shards:
            for key, sample in self._iter_shard(shard):
                i += 1
                if subshard and i % stride != offset:
                    continue
                if injector is not None and injector.io_error_tick():
                    # injected read fault counts against the poison budget so
                    # the skip accounting itself is exercised by drills
                    skip_budget.record(IOError('[fault-inject] sample read'), f'{shard}:{key}')
                    continue
                try:
                    decoded = self._decode(sample)
                except Exception as e:
                    # undecodable member = poison, not transient: skip within
                    # budget instead of killing the epoch (or hiding it)
                    skip_budget.record(e, f'{shard}:{key}')
                    continue
                if decoded is None:
                    continue
                if self.shuffle_size:
                    buf.append(decoded)
                    if len(buf) >= self.shuffle_size:
                        j = rng.randrange(len(buf))
                        yield buf.pop(j)
                else:
                    yield decoded
        while buf:
            j = rng.randrange(len(buf))
            yield buf.pop(j)


class ReaderTfds:
    """tensorflow_datasets wrapper (reference reader_tfds.py:70-340).

    Requires `tensorflow_datasets` (not shipped in this image). Shard
    distribution uses the same `assign_shards` round-robin over
    global workers; fine-grained even splits fall back to sample striding.
    """

    def __init__(self, root, name, split='train', is_training=False, batch_size=None,
                 seed=42, input_img_mode='RGB', dist_rank=0, dist_num_replicas=1, **kwargs):
        try:
            import tensorflow_datasets as tfds  # noqa: F401
        except ImportError as e:
            raise ImportError(
                'ReaderTfds requires tensorflow_datasets, which is not installed in this '
                'environment. Use a wds/ shard set or folder dataset instead.') from e
        import tensorflow_datasets as tfds
        self.builder = tfds.builder(name, data_dir=root or None)
        self.split = split
        self.is_training = is_training
        self.seed = seed
        self.input_img_mode = input_img_mode
        self.dist_rank = dist_rank
        self.dist_num_replicas = dist_num_replicas
        self.num_workers = 1
        self.worker_id = 0
        self.epoch = -1
        self.split_info = self.builder.info.splits[split.split('[')[0]]
        try:
            # sliced splits ('train[:10%]') report their sliced count
            self.num_samples = self.builder.info.splits[split].num_examples
        except (KeyError, ValueError) as e:
            _logger.debug(f'No sliced count for tfds split {split!r} ({e!r}); '
                          f'using the full-split count')
            self.num_samples = self.split_info.num_examples

    def set_epoch(self, epoch: int):
        self.epoch = epoch

    def set_worker_info(self, worker_id: int, num_workers: int):
        self.worker_id = worker_id
        self.num_workers = max(1, num_workers)

    def __len__(self):
        return self.num_samples

    def __iter__(self):
        import tensorflow_datasets as tfds
        from PIL import Image
        global_num_workers = self.dist_num_replicas * self.num_workers
        global_worker_id = self.dist_rank * self.num_workers + self.worker_id
        subsplit = None
        input_context = None
        if global_num_workers > 1:
            if self.split_info.num_shards < global_num_workers or not self.is_training:
                subsplit = tfds.even_splits(self.split, global_num_workers)[global_worker_id]
            else:
                import tensorflow as tf
                input_context = tf.distribute.InputContext(
                    num_input_pipelines=global_num_workers,
                    input_pipeline_id=global_worker_id,
                    num_replicas_in_sync=self.dist_num_replicas)
        read_config = tfds.ReadConfig(
            shuffle_seed=self.seed + max(self.epoch, 0),
            shuffle_reshuffle_each_iteration=True,
            input_context=input_context)
        ds = self.builder.as_dataset(
            split=subsplit or self.split,
            shuffle_files=self.is_training,
            read_config=read_config)
        for ex in ds.as_numpy_iterator():
            img = Image.fromarray(ex['image'])
            if self.input_img_mode and img.mode != self.input_img_mode:
                img = img.convert(self.input_img_mode)
            yield img, int(ex.get('label', -1))


class ReaderHfids:
    """Hugging Face streaming (IterableDataset) reader
    (reference readers/reader_hfids.py:29). `name` is a hub dataset or a local
    builder such as 'imagefolder' (with `root` as its data_dir), loaded with
    streaming=True; shards are distributed with .shard() and training epochs
    use the builtin buffered shuffle keyed on (seed, epoch)."""

    def __init__(
            self,
            name: str,
            root: Optional[str] = None,
            split: str = 'train',
            is_training: bool = False,
            seed: int = 42,
            shuffle_size: int = 2048,
            input_key: str = 'image',
            input_img_mode: str = 'RGB',
            target_key: str = 'label',
            dist_rank: int = 0,
            dist_num_replicas: int = 1,
    ):
        import datasets as hfds
        split = {'val': 'validation'}.get(split, split)
        load_kwargs = {}
        if name in ('imagefolder',):
            load_kwargs['data_dir'] = root
        else:
            load_kwargs['cache_dir'] = root or None
        self.ds = hfds.load_dataset(name, split=split, streaming=True, **load_kwargs)
        self.is_training = is_training
        self.seed = seed
        self.shuffle_size = shuffle_size if is_training else 0
        self.input_key = input_key
        self.input_img_mode = input_img_mode
        self.target_key = target_key
        self.dist_rank = dist_rank
        self.dist_num_replicas = dist_num_replicas
        self.num_workers = 1
        self.worker_id = 0
        self.epoch = -1
        self.num_samples = getattr(self.ds.info.splits.get(split), 'num_examples', None) \
            if getattr(self.ds, 'info', None) and self.ds.info.splits else None

    def set_epoch(self, epoch: int):
        self.epoch = epoch

    def set_worker_info(self, worker_id: int, num_workers: int):
        self.worker_id = worker_id
        self.num_workers = num_workers

    def __len__(self):
        if self.num_samples is None:
            raise TypeError('streaming hfids dataset length unknown')
        return self.num_samples

    def __iter__(self):
        ds = self.ds
        # shuffle FIRST so the stride-split fallback below still sees a
        # shuffled stream (a raw generator can't be shuffled)
        if self.is_training and self.shuffle_size:
            ds = ds.shuffle(seed=self.seed + max(self.epoch, 0), buffer_size=self.shuffle_size)
        total_shards = self.dist_num_replicas * self.num_workers
        index = self.dist_rank * self.num_workers + self.worker_id
        if total_shards > 1:
            try:
                ds = ds.shard(num_shards=total_shards, index=index)
            except Exception as e:
                # unshardable stream: fall back to stride-based sample split
                _logger.warning(f'hfids stream is not shardable ({e!r}); falling back '
                                f'to stride-{total_shards} sample interleave')
                ds = (s for i, s in enumerate(ds) if i % total_shards == index)
        for item in ds:
            img = item[self.input_key]
            if hasattr(img, 'convert') and self.input_img_mode and img.mode != self.input_img_mode:
                img = img.convert(self.input_img_mode)
            yield img, item[self.target_key]
