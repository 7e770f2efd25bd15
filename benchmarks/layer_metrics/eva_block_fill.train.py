"""Needed pairs (`attn.eva_pairs`) over the pairs in the tiles the core visits (`attn.eva_blocks` x the tile's area x the
heads held): what the block shape wastes on the windows' diagonals and on the summaries' partly seen block."""
LAYER = 'attention'
UNIT = '%'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import cla_lm_readers
    return cla_lm_readers.READERS['eva_block_fill.train'].read(run)
