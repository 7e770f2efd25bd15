"""Roofline share of the core under the block-diffusion mask (compute-bound), on the NEEDED pairs (L^2 + L K a
layer of a noised copy beside the clean sequence; the last layer its noised queries' alone), over the device time
under `swa.attn.core_bd`, over the bf16 peak: a core that multiplies tiles the mask excludes reads lower, never
higher."""
LAYER = 'attention'
UNIT = '%'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import bd_lm_readers
    return bd_lm_readers.READERS['attn_bd_core_mfu.train'].read(run)
