"""Device busy time (union of op intervals) inside the traced part of the
window, per training step dispatched in it. Includes the loader's on-device
augment program, which runs once a step."""
LAYER = 'step'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    trace = run.get('trace')
    if run.get('runner') != 'train' or not trace or not trace.get('work'):
        return None
    return trace['busy_s'] / trace['work'] * 1e3
