"""Needed pairs of the block-diffusion mask over `attn.bd_blocks` x the tile's area: what the block shape wastes
along the clean diagonal and, most of all, the noised one (blocks of 4 in tiles of 1024)."""
LAYER = 'attention'
UNIT = '%'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import bd_lm_readers
    return bd_lm_readers.READERS['attn_bd_block_fill.train'].read(run)
