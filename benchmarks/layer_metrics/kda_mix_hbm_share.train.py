"""Roofline share of the gated delta-rule mixer's elementwise middle, its one memory-bound part: the bytes the taps, SiLU,
L2 norms, decay, beta and the gated head norm need to move once in a step (`harness/kda_lm_flops.mix_bytes` of the step's
`kda.rows`: 30 x 1024 x 2 B a position and layer) over the device time under `kda.mix`, over the chip's HBM bandwidth."""
LAYER = 'delta attention'
UNIT = '%'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import kda_lm_readers
    return kda_lm_readers.READERS['kda_mix_hbm_share.train'].read(run)
