"""Device ms a traced step under `glm.head_loss`: the final norm, the head's product in chunks and the loss, forward
and backward; where embedding and head are one leaf (`lfm2_8b_a1b_ep4_train_8k`), that leaf's second gradient."""
LAYER = 'step'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import sconv_lm_readers
    return sconv_lm_readers.READERS['head_device_ms.train'].read(run)
