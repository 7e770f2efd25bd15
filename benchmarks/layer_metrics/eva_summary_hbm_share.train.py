"""Roofline share of the chunk summaries, the one memory-bound part of the mixer: the bytes they need to move in a step
(`harness/cla_lm_flops.summary_bytes`: k and v read, the summaries written, and the same with gradients backward) over
the device time under `evabyte.attn.summary`, over the chip's HBM bandwidth."""
LAYER = 'attention'
UNIT = '%'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import cla_lm_readers
    return cla_lm_readers.READERS['eva_summary_hbm_share.train'].read(run)
