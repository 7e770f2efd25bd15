"""Roofline share of the window layers' causal core (compute-bound), on the NEEDED pairs (i - j < window), over
the device time under `swa.attn.core_window`, over the bf16 peak: a core that multiplies tiles the window
excludes reads lower, never higher."""
LAYER = 'attention'
UNIT = '%'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import swa_lm_readers
    return swa_lm_readers.READERS['attn_window_core_mfu.train'].read(run)
