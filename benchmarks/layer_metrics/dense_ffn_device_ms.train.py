"""Device ms a traced step under `glm.dense_ffn`: the dense SwiGLU of a decoder's leading layers (width 7168 in
`lfm2_8b_a1b_ep4_train_8k`, 10240 in `glm47_flash_ep8_train_8k`), forward and backward."""
LAYER = 'feed-forward'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import sconv_lm_readers
    return sconv_lm_readers.READERS['dense_ffn_device_ms.train'].read(run)
