"""Roofline share of the gated short convolution's two products (compute-bound): 4 x 2048^2 MACs a token and layer
x 6, forward and backward, over the device time under `sconv.proj`, over the bf16 peak."""
LAYER = 'short convolution'
UNIT = '%'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import sconv_lm_readers
    return sconv_lm_readers.READERS['sconv_proj_mfu.train'].read(run)
