"""The worker threads' `loader.decode_busy_ns` added over the window, over the
window's length times the number of workers: how full the decode pool is.
Near 100 = the loader cannot go faster with these threads."""
LAYER = 'input'
UNIT = '%'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import program_spans
    return program_spans.decode_busy_share(run)
