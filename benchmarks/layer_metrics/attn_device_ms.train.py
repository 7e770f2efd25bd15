"""Device ms a step under `swa.attn.*`: the q/k/v/o products with norm and rotary turn, and both kinds of core."""
LAYER = 'attention'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import swa_lm_readers
    return swa_lm_readers.READERS['attn_device_ms.train'].read(run)
