"""Wall seconds of the program's `setup.data_build` span: datasets and both
loaders (the evaluation loader too, which a benchmark run never uses)."""
LAYER = 'entry and compile cache'
UNIT = 's'
MOVES = 'setup_s'


def read(run: dict):
    from benchmarks.harness import program_spans
    return program_spans.setup_s(run, 'setup.data_build')
