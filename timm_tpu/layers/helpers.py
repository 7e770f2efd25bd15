"""Small shape/arg helpers (reference: timm/layers/helpers.py)."""
from __future__ import annotations

import collections.abc
from itertools import repeat


def _ntuple(n):
    def parse(x):
        if isinstance(x, collections.abc.Iterable) and not isinstance(x, str):
            return tuple(x)
        return tuple(repeat(x, n))
    return parse


to_1tuple = _ntuple(1)
to_2tuple = _ntuple(2)
to_3tuple = _ntuple(3)
to_4tuple = _ntuple(4)
to_ntuple = _ntuple


def make_divisible(v, divisor: int = 8, min_value=None, round_limit: float = 0.9):
    min_value = min_value or divisor
    new_v = max(min_value, int(v + divisor / 2) // divisor * divisor)
    # Make sure that round down does not go down by more than 10%.
    if new_v < round_limit * v:
        new_v += divisor
    return new_v


def extend_tuple(x, n):
    # pad a possibly-scalar value to a tuple of length n by repeating the last element
    if not isinstance(x, (tuple, list)):
        x = (x,)
    else:
        x = tuple(x)
    pad_n = n - len(x)
    if pad_n <= 0:
        return x[:n]
    return x + (x[-1],) * pad_n


def head_slice(whole, axis: int, offset: int, held: int, width: int):
    """Heads `offset` .. `offset + held` of an array that holds its heads side by side along `axis`, `width` entries a
    head: what a share of a head-parallel layer takes of a leaf of the whole layer."""
    index = [slice(None)] * whole.ndim
    index[axis] = slice(offset * width, (offset + held) * width)
    return whole[tuple(index)]
