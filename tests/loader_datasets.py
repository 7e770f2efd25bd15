"""Datasets for `test_loader_processes.py`, in a module of their own so that a
decode process (which is handed `tests/` through PYTHONPATH) can unpickle them.
"""
import random

import numpy as np


class Numbered:
    """Item i is a 2 x 2 x 3 uint8 image filled with i % 251 and the target i.
    `poison`: indices that always raise; `flaky`: indices whose first read in a
    process raises an OSError; `draws`: the image is instead one draw from each of
    the two global generators the transforms use. `decodes_files` (set by the
    test) sends it to the loader's process stage or to its thread stage."""

    def __init__(self, n, decodes_files, poison=(), flaky=(), draws=False):
        self.n, self.decodes_files = n, decodes_files
        self.poison, self.flaky, self.draws = set(poison), set(flaky), draws
        self.seen = set()

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        if i in self.poison:
            raise ValueError(f'poisoned sample {i}')
        if i in self.flaky and i not in self.seen:
            self.seen.add(i)
            raise OSError(f'transient fault on sample {i}')
        if self.draws:
            return np.asarray([random.random(), np.random.rand()], np.float64).reshape(1, 1, 2), i
        return np.full((2, 2, 3), i % 251, np.uint8), i
