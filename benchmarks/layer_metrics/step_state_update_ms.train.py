"""Median over the window's steps of the program's `task.state_update` span:
`nnx.update` of the module with the new state, EMA and sentinel state kept."""
LAYER = 'step'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import program_spans
    return program_spans.step_ms(run, 'task.state_update')
