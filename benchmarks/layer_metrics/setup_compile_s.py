"""Sum of the `xla.backend_compile` spans before the window: every program built
or read back during set-up, the small ones under the persistence threshold too."""
LAYER = 'entry and compile cache'
UNIT = 's'
MOVES = 'setup_s'


def read(run: dict):
    from benchmarks.harness import program_spans
    return program_spans.setup_s(run, 'xla.backend_compile')
