"""Device ms a step under the gated delta-rule mixer's scopes (`kda.proj`, `kda.mix`, `kda.core`): the products with the norm
before them, the elementwise middle behind its barriers, and the chunked recurrence, forward, rematerialised and backward."""
LAYER = 'delta attention'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import kda_lm_readers
    return kda_lm_readers.READERS['kda_device_ms.train'].read(run)
