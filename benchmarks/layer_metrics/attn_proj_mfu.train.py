"""Roofline share of the q/k/v/o products (compute-bound) over the device time under `swa.attn.proj`, over the
bf16 peak."""
LAYER = 'attention'
UNIT = '%'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import swa_lm_readers
    return swa_lm_readers.READERS['attn_proj_mfu.train'].read(run)
