"""Roofline share of the gated short convolution's middle, the one memory-bound part of the mixer: the bytes the two
gates and the taps need to move in a step (`harness/sconv_lm_flops.mix_bytes` of the step's `sconv.rows`: 11 x 2048 x 2 B
a position and layer) over the device time under `sconv.mix`, over the chip's HBM bandwidth. The program keeps the
middle's inputs and outputs behind barriers, so the scope holds all of the middle and nothing of the products."""
LAYER = 'short convolution'
UNIT = '%'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import sconv_lm_readers
    return sconv_lm_readers.READERS['sconv_mix_hbm_share.train'].read(run)
