"""The chunked recurrence's share of its roofline, on the RECURRENCE's own count: 4 x 128 x 128 MACs a position and head
(`harness/kda_lm_flops.py`), x 6, over the device time under `kda.core`, over the bf16 peak. The chunked form's extra
products and its float32 passes are not needed work: a later chunk size or kernel is read against the same yardstick."""
LAYER = 'delta attention'
UNIT = '%'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import kda_lm_readers
    return kda_lm_readers.READERS['kda_core_mfu.train'].read(run)
