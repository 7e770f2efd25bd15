"""Device idle time a traced step that falls under the program's
`task.train_step` spans in the trace's host plane (`program_spans.idle_by_layer`)."""
LAYER = 'device'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import program_spans
    idle = program_spans.idle_by_layer(run)
    return None if idle is None else idle['step']
