"""Roofline share of the full layers' causal core (compute-bound): the S(S+1)/2 pairs' operations, forward and
backward, over the device time under `swa.attn.core_full`, over the bf16 peak."""
LAYER = 'attention'
UNIT = '%'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import swa_lm_readers
    return swa_lm_readers.READERS['attn_full_core_mfu.train'].read(run)
