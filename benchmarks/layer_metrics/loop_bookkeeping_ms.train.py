"""Median over the window's steps of `train.bookkeeping` + `train.log_sync`:
`train.py`'s own tail of a step (scheduler update, recovery-interval check,
fault and shutdown poll, the loss read at --log-interval)."""
LAYER = 'entry and compile cache'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import program_spans
    return program_spans.step_ms(run, 'train.bookkeeping', 'train.log_sync')
