"""Needed window pairs over `attn.window_blocks` x the tile's area: what the block shape wastes at the window's
two edges."""
LAYER = 'attention'
UNIT = '%'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import swa_lm_readers
    return swa_lm_readers.READERS['attn_window_block_fill.train'].read(run)
