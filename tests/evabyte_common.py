"""The toy sizes the EvaByte test files share: hidden 64, 4 heads of width 16, windows of 32 and chunks of 4 at
N = 128 (4 windows, 32 summaries) in query blocks of 16, a SwiGLU of 160, 2 layers, the 320 ids, 8 prediction heads."""
import jax.numpy as jnp
import numpy as np

TOL = 1e-4
SIZES = dict(vocab_size=320, hidden_size=64, intermediate_size=160, num_hidden_layers=2, num_attention_heads=4,
             heads_held=4, head_offset=0, head_dim=16, window_size=32, chunk_size=4, num_pred_heads=8,
             rope_theta=1e5, rms_norm_eps=1e-5)
N, W, C = 128, 32, 4


def batch(seed=0, rows=2):
    """(ids, target) (rows, N) int32: uniform bytes, `target[i]` the id after position i, -1 at the end."""
    ids = np.random.default_rng(seed).integers(0, 320, (rows, N + 1))
    target = np.concatenate([ids[:, 1:N], np.full((rows, 1), -1)], axis=1)
    return jnp.asarray(ids[:, :N], jnp.int32), jnp.asarray(target, jnp.int32)
