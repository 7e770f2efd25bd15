"""Roofline share of the chunk-pooled attention's core (compute-bound), on the NEEDED pairs of both kinds (a window's
single keys, causal, and the summaries of all earlier windows) x 256 MACs x the heads held x the layers x 6, over the
device time under `evabyte.attn.core`, over the bf16 peak: a core that multiplies masked tiles reads lower, never higher."""
LAYER = 'attention'
UNIT = '%'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import cla_lm_readers
    return cla_lm_readers.READERS['eva_core_mfu.train'].read(run)
