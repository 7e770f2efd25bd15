"""Wall seconds of the first `task.step_call`: tracing the step, then compiling
it or reading it from the persistent cache, then the first dispatch."""
LAYER = 'entry and compile cache'
UNIT = 's'
MOVES = 'setup_s'


def read(run: dict):
    from benchmarks.harness import program_spans
    return program_spans.setup_s(run, 'task.step_call', first_only=True)
