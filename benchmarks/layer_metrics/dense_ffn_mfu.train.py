"""Roofline share of the leading dense SwiGLU (compute-bound): its three products' operations (the record's
`needed_macs['dense_ffn']`), forward and backward, over the device time under `glm.dense_ffn`, over the bf16 peak."""
LAYER = 'feed-forward'
UNIT = '%'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import sconv_lm_readers
    return sconv_lm_readers.READERS['dense_ffn_mfu.train'].read(run)
