"""Operations the SDAR share needs for one block-diffusion training step, from
its shapes alone, as `lm_flops.py` and `swa_lm_flops.py` count the other
shares': multiply-accumulates of the forward pass's matrix products by part
(norms, softmax, activations, the rotary turn and the embedding lookup left
out). A training step needs the forward pass once and twice that for the
backward pass: FLOP = MACs x 2 x 3. Nothing recomputed counts, and nothing the
mask excludes: the core counts the NEEDED (query, key) pairs of the
block-diffusion mask, whatever tiles a kernel visits to cover them, and the
routed experts count the (row, expert) slots the step's own counter saw.

A step runs 2 L rows (L noised, L clean) through every layer but the last. The
loss reads the last layer's noised rows only, so of its clean rows the
mathematics needs K and V and nothing else: no clean queries, no clean-on-clean
pairs, no output projection, no router, no experts.
"""
from __future__ import annotations

from .lm_flops import train_flops  # noqa: F401  the same x 2 x 3


def mask_pairs(length: int, block: int) -> int:
    """(query, key) pairs of the mask over 2 x `length` rows in blocks of `block`: noised on its own noised
    block L K, noised on earlier clean blocks L (L - K) / 2, clean on its own and earlier clean blocks
    L (L + K) / 2: L^2 + L K."""
    return noised_pairs(length, block) + length * (length + block) // 2


def noised_pairs(length: int, block: int) -> int:
    """The pairs of the noised queries alone (a last layer's): L K + L (L - K) / 2."""
    return length * block + length * (length - block) // 2


def forward_macs(sizes: dict, seq_len: int, sequences: int, local_slots: float) -> dict:
    """part -> MACs of one step's forward pass over `sequences` sequences of `seq_len` clean tokens;
    `local_slots` is the step's `moe.local_slots` (all layers)."""
    d, heads, kv, hd = sizes['hidden_size'], sizes['num_attention_heads'], sizes['num_key_value_heads'], sizes['head_dim']
    L, K, before_last = seq_len, sizes['block_length'], sizes['num_hidden_layers'] - 1
    q_and_o, k_and_v = 2 * d * heads * hd, 2 * d * kv * hd
    routed_rows = before_last * 2 * L + L                            # the last layer routes its noised rows only
    return {
        'attn_proj': sequences * (before_last * 2 * L * (q_and_o + k_and_v) + L * q_and_o + 2 * L * k_and_v),
        'attn_core_bd': sequences * (before_last * mask_pairs(L, K) + noised_pairs(L, K)) * heads * 2 * hd,
        'moe_route': sequences * routed_rows * d * sizes['num_experts'],
        'moe_experts': local_slots * 3 * d * sizes['moe_intermediate_size'],
        'head': sequences * L * d * sizes['vocab_held'],
    }
