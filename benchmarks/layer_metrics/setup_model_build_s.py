"""Wall seconds of the program's `setup.model_build` span: `create_model`,
sharded initialisation included."""
LAYER = 'entry and compile cache'
UNIT = 's'
MOVES = 'setup_s'


def read(run: dict):
    from benchmarks.harness import program_spans
    return program_spans.setup_s(run, 'setup.model_build')
