"""Device ms a step under latent attention's scopes: projections, norms, rotary turn, causal core."""
LAYER = 'attention'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import lm_readers
    return lm_readers.READERS['mla_device_ms.train'].read(run)
