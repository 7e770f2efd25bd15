"""The toy sizes the two SDAR test files share: hidden 64, 4 query heads on 2 key/value heads of width 16, blocks
of 4 at L 32 in query blocks of 8, 8 experts top-2 with 2 held, vocabulary 256 whose last row (255) is the mask
token, 3 layers (every layer is of one kind; the last one computes its noised rows only)."""
TOL = 1e-4
SIZES = dict(vocab_held=256, hidden_size=64, num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2, experts_held=2, expert_offset=0,
             rope_theta=1e6, rms_norm_eps=1e-6, block_length=4, mask_token_id=255)
L = 32
K = 4


def seen_by_loops(length: int, block: int):
    """The block-diffusion mask over 2 x length rows, written out rule by rule: rows [0, length) noised, the rest clean."""
    import numpy as np
    mask = np.zeros((2 * length, 2 * length), bool)
    for i in range(length):
        for j in range(length):
            mask[i, j] = j // block == i // block                       # noised i sees noised j of its own block
            mask[i, length + j] = j // block < i // block               # noised i sees clean j of earlier blocks
            mask[length + i, length + j] = j // block <= i // block     # clean i sees clean j of its own and earlier blocks
            mask[length + i, j] = False                                 # clean i sees no noised row
    return mask
