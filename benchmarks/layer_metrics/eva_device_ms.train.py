"""Device ms a traced step under the chunk-pooled linear attention's scopes (`evabyte.attn.proj`, `.summary`, `.core`):
the q/k/v/o products with the norm before them and the rotary turn, the chunk summaries, the joint core."""
LAYER = 'attention'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import cla_lm_readers
    return cla_lm_readers.READERS['eva_device_ms.train'].read(run)
