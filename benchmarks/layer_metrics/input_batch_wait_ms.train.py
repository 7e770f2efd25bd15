"""Median over the window's steps of the program's `loader.batch_wait` span: the
main thread blocked on the collator's queue. Near zero while the decode pool
keeps ahead."""
LAYER = 'input'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import program_spans
    return program_spans.step_ms(run, 'loader.batch_wait')
