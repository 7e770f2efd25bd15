"""Device ms a traced step under `img.conv_dw` (`models/convnext.py` `ConvNeXtBlock`): the 7x7 depthwise
convolution, forward, input gradient and weight gradient."""
LAYER = 'step'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import step_scopes
    return step_scopes.scope_ms(run, 'img.conv_dw')
