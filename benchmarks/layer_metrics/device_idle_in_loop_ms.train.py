"""Device idle time a traced step that falls under the rest of `train.step`:
`train.py`'s bookkeeping between the step call and the next fetch."""
LAYER = 'device'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import program_spans
    idle = program_spans.idle_by_layer(run)
    return None if idle is None else idle['loop']
