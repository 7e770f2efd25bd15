"""Device ms a traced step under `evabyte.ffn`: the dense SwiGLU of every layer with its norm and float32 residual add."""
LAYER = 'feed-forward'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import cla_lm_readers
    return cla_lm_readers.READERS['ffn_device_ms.train'].read(run)
