"""Datasets (reference: timm/data/dataset.py:21-207)."""
from __future__ import annotations

import io
import logging
import os
from typing import Callable, Optional

import numpy as np
from PIL import Image

from .readers import create_reader

_logger = logging.getLogger(__name__)

__all__ = ['ImageDataset', 'AugMixDataset']


class ImageDataset:
    decodes_files = True    # an item is a file opened, decoded and transformed: the loader decodes it in a worker process

    def __init__(
            self,
            root: str,
            reader=None,
            split: str = 'train',
            class_map='',
            input_img_mode: str = 'RGB',
            transform: Optional[Callable] = None,
            target_transform: Optional[Callable] = None,
            **kwargs,
    ):
        if reader is None or isinstance(reader, str):
            reader = create_reader(reader or '', root=root, split=split, class_map=class_map)
        self.reader = reader
        self.input_img_mode = input_img_mode
        self.transform = transform
        self.target_transform = target_transform
        self._consecutive_errors = 0

    def __getitem__(self, index: int):
        img, target = self.reader[index]
        try:
            img = Image.open(img)
            img.load()
            self._consecutive_errors = 0
        except Exception as e:
            _logger.warning(f'Skipped sample (index {index}, file {self.reader.filename(index)}). {str(e)}')
            self._consecutive_errors += 1
            if self._consecutive_errors < 50:
                return self[(index + 1) % len(self.reader)]
            raise e
        if self.input_img_mode and img.mode != self.input_img_mode:
            img = img.convert(self.input_img_mode)
        if self.transform is not None:
            img = self.transform(img)
        if target is None:
            target = -1
        elif self.target_transform is not None:
            target = self.target_transform(target)
        return img, target

    def __len__(self):
        return len(self.reader)

    def filename(self, index, basename=False, absolute=False):
        return self.reader.filename(index, basename, absolute)

    def filenames(self, basename=False, absolute=False):
        return self.reader.filenames(basename, absolute)


class IterableImageDataset:
    """Wraps an iterable (streaming) reader with transforms
    (reference dataset.py IterableImageDataset)."""

    def __init__(
            self,
            root: str,
            reader=None,
            transform: Optional[Callable] = None,
            target_transform: Optional[Callable] = None,
            **kwargs,
    ):
        assert reader is not None, 'IterableImageDataset requires a constructed streaming reader'
        self.reader = reader
        self.transform = transform
        self.target_transform = target_transform

    def __iter__(self):
        for img, target in self.reader:
            if self.transform is not None:
                img = self.transform(img)
            if self.target_transform is not None:
                target = self.target_transform(target)
            yield img, target

    def __len__(self):
        return len(self.reader)

    def set_epoch(self, epoch: int):
        if hasattr(self.reader, 'set_epoch'):
            self.reader.set_epoch(epoch)

    def set_worker_info(self, worker_id: int, num_workers: int):
        if hasattr(self.reader, 'set_worker_info'):
            self.reader.set_worker_info(worker_id, num_workers)


class AugMixDataset:
    """Returns (clean, aug1..augN) tuples for JSD training
    (reference dataset.py:170)."""
    decodes_files = True

    def __init__(self, dataset: ImageDataset, num_splits: int = 2):
        self.dataset = dataset
        self.num_splits = num_splits
        self.augmentation = None
        self.normalize = None

    def _set_transforms(self, x):
        assert isinstance(x, (list, tuple)) and len(x) == 3
        self.dataset.transform = x[0]
        self.augmentation = x[1]
        self.normalize = x[2]

    @property
    def transform(self):
        return self.dataset.transform

    @transform.setter
    def transform(self, x):
        self._set_transforms(x)

    def _normalize(self, x):
        return x if self.normalize is None else self.normalize(x)

    def __getitem__(self, i):
        x, y = self.dataset[i]  # all splits share the same initial transform
        x_list = [self._normalize(x)]
        for _ in range(self.num_splits - 1):
            x_list.append(self._normalize(self.augmentation(x)))
        return tuple(x_list), y

    def __len__(self):
        return len(self.dataset)


class TokenWindows:
    """A flat file of token ids (raw little-endian int32, memory-mapped) cut
    into consecutive windows of `seq_len`: sample i is `(ids[i*S:(i+1)*S],
    target)` with `target[j]` the id after `ids[j]` and -1 (the causal-LM
    task's IGNORE) at the window's last position. Windows do not overlap, so
    an epoch gives no sequence twice; causal attention runs over the whole
    window (no document boundaries are marked in a flat stream)."""

    def __init__(self, path: str, seq_len: int, vocab_size: Optional[int] = None):
        if not os.path.isfile(path):
            raise FileNotFoundError(f'no token file {path} (raw int32 ids)')
        self.ids = np.memmap(path, dtype='<i4', mode='r')
        self.seq_len = int(seq_len)
        if len(self.ids) < self.seq_len:
            raise ValueError(f'{path} holds {len(self.ids)} ids, fewer than one window of {self.seq_len}')
        self.vocab_size = vocab_size

    def __len__(self):
        return len(self.ids) // self.seq_len

    def __getitem__(self, index):
        start = int(index) * self.seq_len
        ids = np.array(self.ids[start:start + self.seq_len], np.int32)
        if self.vocab_size is not None and (ids.min() < 0 or ids.max() >= self.vocab_size):
            raise ValueError(f'window {index}: ids outside [0, {self.vocab_size})')
        target = np.empty_like(ids)
        target[:-1], target[-1] = ids[1:], -1
        return ids, target
