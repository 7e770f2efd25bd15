"""Median over the window's steps of the program's `task.scalars_put` span: the
two `jnp.asarray` scalar transfers (learning rate, EMA decay) a step."""
LAYER = 'step'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import program_spans
    return program_spans.step_ms(run, 'task.scalars_put')
