"""Median wall time from one `train_step` returning to the next returning,
over the window: the pace the whole loop keeps, steadier than the window's
rate because one slow step does not move a median."""
import statistics

LAYER = 'step'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    spans = run.get('spans', {})
    between, inside = spans.get('loader_next_s'), spans.get('train_step_dispatch_s')
    if not between or not inside:
        return None
    return statistics.median(b + d for b, d in zip(between, inside[1:])) * 1e3
