"""Roofline share of the gated delta-rule mixer's products (compute-bound): 18.1M MACs a token and layer of the share
(q, k, v, o, two low-rank gates, beta) x 6, forward and backward, over the device time under `kda.proj`, over the bf16 peak."""
LAYER = 'delta attention'
UNIT = '%'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import kda_lm_readers
    return kda_lm_readers.READERS['kda_proj_mfu.train'].read(run)
