"""Roofline share of the grouped products (compute-bound): the counted slots' operations, forward and backward,
over the device time under `glm.moe.experts`, over the bf16 peak. Every language-model family whose step runs
under these scopes has it: the reader asks for no family."""
LAYER = 'experts'
UNIT = '%'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import lm_readers
    return lm_readers.READERS['moe_experts_mfu.train'].read(run)
