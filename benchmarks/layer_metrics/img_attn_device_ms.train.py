"""Device ms a traced step under `img.attn.qkv`, `img.attn.core` and `img.attn.proj` (`layers/attention.py`
`Attention`): the fused qkv product with its head transpose, scores + softmax + P V, and the output product with
the transpose back. Its LayerNorms are `img_norm_device_ms.train`'s (the innermost scope counts)."""
LAYER = 'attention'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import step_scopes
    return step_scopes.scope_ms(run, 'img.attn.qkv', 'img.attn.core', 'img.attn.proj')
