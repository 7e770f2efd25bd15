"""Median host time between one `train_step` returning and the next being
called (the wrapper's two clocks): the loader's `next()` plus `train.py`'s
own loop. Near zero while the prefetcher stays ahead of the device."""
import statistics

LAYER = 'input'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    between = run.get('spans', {}).get('loader_next_s')
    if not between:
        return None
    return statistics.median(between) * 1e3
