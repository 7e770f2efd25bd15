"""Roofline share of the dense SwiGLU (compute-bound): its three products' operations, forward and backward, over the
device time under `evabyte.ffn`, over the bf16 peak."""
LAYER = 'feed-forward'
UNIT = '%'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import cla_lm_readers
    return cla_lm_readers.READERS['ffn_mfu.train'].read(run)
