"""Model FLOP/s utilisation of the device's busy time: the operations the
forward and backward passes need for one step (the record's
`needed_step_flops`, which the cell's runner put there from its family's shape
functions, `harness/flops.py`, `lm_flops.py`, `swa_lm_flops.py`: forward MACs
x 2 x 3, a language model's routed experts by the step's own
`moe.local_slots`, nothing recomputed) over `step_device_ms.train`, over the
chip's bfloat16 peak. One share of the whole step's peak, in every training
cell: the work is the configuration's, whatever implements it."""
LAYER = 'step'
UNIT = '%'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import peaks
    trace, needed = run.get('trace'), run.get('needed_step_flops')
    if run.get('runner') != 'train' or not trace or not trace.get('work') or not needed:
        return None
    step_s = trace['busy_s'] / trace['work']
    return 100.0 * needed / step_s / peaks.peak(run['device_kind'])['bf16_flops']
