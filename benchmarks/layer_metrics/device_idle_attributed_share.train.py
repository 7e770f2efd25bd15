"""Idle time of the traced window that lies under any of the program's loop
spans, over all of it. Low = the device idles where the program has no span."""
LAYER = 'device'
UNIT = '%'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import program_spans
    idle = program_spans.idle_by_layer(run)
    return None if idle is None else idle['share']
