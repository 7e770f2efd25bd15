"""Operations and bytes the LFM2 share needs, from its shapes alone, as
`lm_flops.py` counts the GLM share's: multiply-accumulates of the forward
pass's matrix products by part (norms, softmax, activations, gates, taps, the
rotary turn and the embedding lookup left out). A training step needs the
forward pass once and twice that for the backward pass: FLOP = MACs x 2 x 3.
Nothing recomputed counts, and nothing a mask excludes: the one attention
layer's core counts the S(S+1)/2 causal pairs at its head width of 64 — the
NEEDED pairs, whatever a kernel pads or visits to cover them — and the routed
experts count the (token, expert) slots the step's own counter saw. The gated
short convolution's middle (two gates, three taps) is the one part bound by
memory: it has a byte count and no operation count.
"""
from __future__ import annotations

from .lm_flops import train_flops  # noqa: F401  the same x 2 x 3
from .swa_lm_flops import causal_pairs


def layer_kinds(sizes: dict) -> tuple:
    """(conv layers, attention layers, dense feed-forwards, expert layers) among the layers held."""
    kinds = list(sizes['layer_types'])[:sizes['num_hidden_layers']]
    dense = min(sizes['num_dense_layers'], len(kinds))
    return kinds.count('conv'), kinds.count('full_attention'), dense, len(kinds) - dense


def forward_macs(sizes: dict, seq_len: int, sequences: int, local_slots: float) -> dict:
    """part -> MACs of one step's forward pass over `sequences` x `seq_len` tokens; `local_slots` is the step's
    `moe.local_slots` (all expert layers)."""
    d, heads, kv, hd = sizes['hidden_size'], sizes['num_attention_heads'], sizes['num_key_value_heads'], sizes['head_dim']
    tokens = seq_len * sequences
    conv, attn, dense, moe = layer_kinds(sizes)
    return {
        'sconv_proj': tokens * conv * (d * 3 * d + d * d),                       # `in_proj` to three gates' worth, `out_proj` back
        'attn_proj': tokens * attn * (2 * d * heads * hd + 2 * d * kv * hd),
        'attn_core_full': causal_pairs(seq_len) * sequences * attn * heads * 2 * hd,     # q k^T and p v, every query head
        'dense_ffn': tokens * dense * 3 * d * sizes['intermediate_size'],
        'moe_route': tokens * moe * d * sizes['num_experts'],
        'moe_experts': local_slots * 3 * d * sizes['moe_intermediate_size'],
        'head': tokens * d * sizes['vocab_held'],                                # the tied head: the embedding's rows, read once more
    }


def mix_bytes(rows: float, dim: int = 2048, itemsize: int = 2) -> float:
    """Bytes the middles NEED to move in a training step for `rows` positions x conv layers (the step's
    `sconv.rows`), where the two products are operations of their own: forward, the product's output read (3 x dim
    a row: b, c, u) and the gated result written (1); backward, the product's output and the result's gradient read
    (3 + 1) and the product's gradient written (3). 11 x dim x `itemsize` (2: bfloat16) a row; the taps, 3 x dim
    numbers a layer, are left out."""
    return rows * dim * itemsize * 11
