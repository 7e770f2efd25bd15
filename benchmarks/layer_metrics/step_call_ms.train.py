"""Median over the window's steps of the program's `task.step_call` span: the
jitted call itself — flattening five state trees, dispatch, and any wait the
runtime imposes before it returns."""
LAYER = 'step'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import program_spans
    return program_spans.step_ms(run, 'task.step_call')
