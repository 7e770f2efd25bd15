"""Device ms a traced step under `img.mlp` (`layers/mlp.py` `Mlp`): fc1, activation, fc2, forward and backward;
a ViT block's token MLP and a ConvNeXt block's channels-last pointwise pair."""
LAYER = 'step'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import step_scopes
    return step_scopes.scope_ms(run, 'img.mlp')
