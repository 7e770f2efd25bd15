"""Device ms a step under the expert layers' scopes: routing and dispatch, grouped products, shared expert. Every
language-model family whose step runs under these scopes has it: the reader asks for no family."""
LAYER = 'experts'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import lm_readers
    return lm_readers.READERS['moe_device_ms.train'].read(run)
