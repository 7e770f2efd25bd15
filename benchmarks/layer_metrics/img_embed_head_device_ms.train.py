"""Device ms a traced step at the model's two ends: `img.patch_embed` or `img.stem` and `img.downsample`,
`img.head`, the batch's normalisation inside the step (`step.input`) and the loss (`step.loss`)."""
LAYER = 'step'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import step_scopes
    return step_scopes.scope_ms(run, 'img.patch_embed', 'img.stem', 'img.downsample', 'img.head', 'step.input', 'step.loss')
