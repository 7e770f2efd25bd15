"""Median over the window's steps of `loader.h2d` + `loader.sample_params` +
`loader.augment_call`: what the main thread itself does to a batch — the
host-to-device put, the numpy parameter draws, the augment program's dispatch."""
LAYER = 'input'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import program_spans
    return program_spans.step_ms(run, 'loader.h2d', 'loader.sample_params', 'loader.augment_call')
