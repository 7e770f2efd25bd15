"""Thread-CPU time over wall time of `task.train_step` less `task.sentinel_poll`,
over the window: how much of the call's Python part the main thread ran. Low =
it waited, for the interpreter (the loader's threads) or inside the runtime."""
LAYER = 'step'
UNIT = '%'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import program_spans
    return program_spans.host_cpu_share(run)
