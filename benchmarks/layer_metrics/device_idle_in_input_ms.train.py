"""Device idle time a traced step that falls under `train.loader_next` +
`train.batch_to_device`: the device waiting for its next batch."""
LAYER = 'device'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import program_spans
    idle = program_spans.idle_by_layer(run)
    return None if idle is None else idle['input']
