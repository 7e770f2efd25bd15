"""Device ms a traced step under the gated short convolution's scopes (`sconv.proj`, `sconv.mix`): the two
products with the norm before them, the two elementwise gates and the causal depthwise taps, forward and backward."""
LAYER = 'short convolution'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import sconv_lm_readers
    return sconv_lm_readers.READERS['sconv_device_ms.train'].read(run)
