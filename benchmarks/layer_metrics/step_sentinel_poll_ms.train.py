"""Median over the window's steps of the program's `task.sentinel_poll` span:
`sentinel.observe()`, whose `device_get` waits for the step just dispatched.
About one device step long = host and device run in turn."""
LAYER = 'step'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import program_spans
    return program_spans.step_ms(run, 'task.sentinel_poll')
