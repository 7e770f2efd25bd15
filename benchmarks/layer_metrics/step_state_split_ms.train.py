"""Median over the window's steps of the program's `task.state_split` span:
`model.train()` + `nnx.split` of the live model at the head of `train_step`."""
LAYER = 'step'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import program_spans
    return program_spans.step_ms(run, 'task.state_split')
