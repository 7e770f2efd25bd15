"""Device ms a traced step after the gradients, in `TrainingTask.train_step`, which every cell runs: the global
norm and clip (`step.clip`), the optimizer (`step.update`), the non-finite guard's reduction and selects
(`step.guard`), the EMA (`step.ema`)."""
LAYER = 'step'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import step_scopes
    return step_scopes.scope_ms(run, 'step.clip', 'step.update', 'step.guard', 'step.ema')
