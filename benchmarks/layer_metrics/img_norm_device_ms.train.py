"""Device ms a traced step under `img.norm` (`layers/norm.py` `LayerNorm` = `LayerNorm2d`), forward and backward,
wherever it is called from: blocks, attention's q/k norms, stem, downsamples, head."""
LAYER = 'step'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import step_scopes
    return step_scopes.scope_ms(run, 'img.norm')
