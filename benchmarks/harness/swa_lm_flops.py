"""Operations the SmallThinker share needs, from its shapes alone, as
`lm_flops.py` counts the GLM share's: multiply-accumulates of the forward
pass's matrix products by part (norms, softmax, activations, the rotary turn
and the embedding lookup left out). A training step needs the forward pass once
and twice that for the backward pass: FLOP = MACs x 2 x 3. Nothing recomputed
counts, and nothing the masks exclude: a full core counts the S(S+1)/2 causal
pairs, a window core the pairs with i - j < window — the NEEDED pairs, whatever
tiles a kernel visits to cover them — and the routed experts count the (token,
expert) slots the step's own counter saw.
"""
from __future__ import annotations

from .lm_flops import train_flops  # noqa: F401  the same x 2 x 3


def causal_pairs(seq_len: int) -> int:
    """(query i, key j) pairs with j <= i."""
    return seq_len * (seq_len + 1) // 2


def window_pairs(seq_len: int, window: int) -> int:
    """Pairs with j <= i and i - j < window: query i sees min(i + 1, window) keys."""
    w = min(window, seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def layer_kinds(sizes: dict) -> tuple:
    """(full layers, window layers) among the layers held."""
    windowed = sum(bool(x) for x in sizes['sliding_window_layout'][:sizes['num_hidden_layers']])
    return sizes['num_hidden_layers'] - windowed, windowed


def forward_macs(sizes: dict, seq_len: int, sequences: int, local_slots: float) -> dict:
    """part -> MACs of one step's forward pass over `sequences` x `seq_len` tokens; `local_slots` is the
    step's `moe.local_slots` (all layers)."""
    d, heads, kv, hd = sizes['hidden_size'], sizes['num_attention_heads'], sizes['num_key_value_heads'], sizes['head_dim']
    tokens, layers = seq_len * sequences, sizes['num_hidden_layers']
    full, windowed = layer_kinds(sizes)
    per_pair = heads * 2 * hd                                        # q k^T and p v, every query head
    return {
        'attn_proj': tokens * layers * (2 * d * heads * hd + 2 * d * kv * hd),
        'attn_core_full': causal_pairs(seq_len) * sequences * full * per_pair,
        'attn_core_window': window_pairs(seq_len, sizes['sliding_window_size']) * sequences * windowed * per_pair,
        'moe_route': tokens * layers * d * sizes['moe_num_primary_experts'],
        'moe_experts': local_slots * 3 * d * sizes['moe_ffn_hidden_size'],
        'head': tokens * d * sizes['vocab_held'],
    }
