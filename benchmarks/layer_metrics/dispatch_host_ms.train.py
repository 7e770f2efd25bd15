"""Median host time inside one `ClassificationTask.train_step` call (the
wrapper's two clocks around it): splitting and updating the model's state in
Python, the jitted call's dispatch, and whatever wait the runtime imposes on
it. Where it is near `step_wall_ms.train`, the loop is paced by this call, not
by the loader."""
import statistics

LAYER = 'step'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    inside = run.get('spans', {}).get('train_step_dispatch_s')
    if not inside:
        return None
    return statistics.median(inside) * 1e3
