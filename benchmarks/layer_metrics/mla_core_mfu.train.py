"""Roofline share of the causal core (compute-bound): the S(S+1)/2 pairs' operations, forward and backward, over
the device time under `glm.mla.core`, over the bf16 peak."""
LAYER = 'attention'
UNIT = '%'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import lm_readers
    return lm_readers.READERS['mla_core_mfu.train'].read(run)
