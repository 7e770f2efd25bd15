"""Model FLOP/s utilisation of the device's busy time: the operations the
forward and backward passes need for one step (shape function in
`harness/flops.py`: forward MACs x 2 x 3, nothing recomputed) over
`step_device_ms.train`, over the chip's bfloat16 peak."""
LAYER = 'step'
UNIT = '%'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import flops, peaks
    trace = run.get('trace')
    if run.get('runner') != 'train' or not trace or not trace.get('work'):
        return None
    step_s = trace['busy_s'] / trace['work']
    needed = flops.train_flops_per_image(run['reference'], run['sizes']) * run['batch_size']
    return 100.0 * needed / step_s / peaks.peak(run['device_kind'])['bf16_flops']
