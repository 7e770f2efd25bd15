"""Operations and bytes the Solar-Open2 share needs, from its shapes alone, as
`lm_flops.py` counts the GLM share's: multiply-accumulates of the forward
pass's matrix products by part (norms, softmax, activations, gates, taps and
the embedding lookup left out). A training step needs the forward pass once and
twice that for the backward pass: FLOP = MACs x 2 x 3. Nothing recomputed
counts, and nothing a mask excludes: the attention layer's core counts the
S(S+1)/2 causal pairs, the routed experts the (token, expert) slots the step's
own counter saw.

The delta rule's core is counted BY THE RECURRENCE, whatever chunk or kernel
computes it: a position of a head decays and reads its state once for the
rule's own value (k^T S: d_k x d_v), writes the rank-one update (k u^T: d_k x
d_v) and reads the output (q^T S: d_k x d_v), plus the decay's multiply of the
state (d_k x d_v): 4 x d_k x d_v MACs. The chunked form multiplies more (the
pair sums, the triangular inverse, W and U) and at float32 in six bfloat16
passes; none of that is needed work, so `kda_core_mfu.train` reads a later
chunk size or a kernel against the same yardstick. The mixer's elementwise
middle (taps, SiLU, the L2 norms, the decay and beta, the gated head norm) is
bound by memory: it has a byte count and no operation count.
"""
from __future__ import annotations

from .lm_flops import train_flops  # noqa: F401  the same x 2 x 3
from .swa_lm_flops import causal_pairs


def layer_kinds(sizes: dict) -> tuple:
    """(KDA layers, attention layers, expert layers) among the layers held."""
    layers = sizes['num_hidden_layers']
    attention = sum(1 for l in sizes['gqa_layers'] if l < layers)
    return layers - attention, attention, layers


def expert_layers(sizes: dict) -> int:
    """Layers of the held model that route: every one (`first_k_dense_replace` 0)."""
    return layer_kinds(sizes)[2]


def kv_heads_held(sizes: dict) -> int:
    return sizes['heads_held'] * sizes['num_key_value_heads'] // sizes['num_attention_heads']


def forward_macs(sizes: dict, seq_len: int, sequences: int, local_slots: float) -> dict:
    """part -> MACs of one step's forward pass over `sequences` x `seq_len` tokens; `local_slots` is the step's
    `moe.local_slots` (all expert layers)."""
    d, hd, rank = sizes['hidden_size'], sizes['head_dim'], sizes['gate_rank']
    wide, kv_wide = sizes['heads_held'] * hd, kv_heads_held(sizes) * hd
    tokens = seq_len * sequences
    kda, attn, moe = layer_kinds(sizes)
    expert = 3 * d * sizes['moe_intermediate_size']
    return {
        # q, k, v and the output product, the two low-rank gates (down and up), beta
        'kda_proj': tokens * kda * (4 * d * wide + 2 * (d * rank + rank * wide) + d * sizes['heads_held']),
        'kda_core': tokens * kda * sizes['heads_held'] * 4 * hd * hd,
        'attn_proj': tokens * attn * (3 * d * wide + 2 * d * kv_wide),                   # q, the gate and o; k and v
        'attn_core_full': causal_pairs(seq_len) * sequences * attn * sizes['heads_held'] * 2 * hd,
        'moe_route': tokens * moe * d * sizes['n_routed_experts'],
        'moe_shared': tokens * moe * expert * sizes['n_shared_experts'],
        'moe_experts': local_slots * expert,
        'head': tokens * d * sizes['vocab_held'],
    }


def mix_bytes(rows: float, wide: int, itemsize: int = 2) -> float:
    """Bytes the middles NEED to move once in a training step for `rows` positions x KDA layers (the step's `kda.rows`),
    `wide` channels held (heads x head_dim), where the products and the core are operations of their own. In units of
    `wide` x `itemsize` (bfloat16) a row: forward, before the core, the three products' outputs and the decay's
    pre-activation read (4), q, k, v written (3) and the decay's log written at float32 (2); after it, the core's output
    and the gate read (2), the gated result written (1): 12. Backward: the same 6 inputs read again, the cotangents of
    what was written read (3 + 2 + 1) and the cotangents of what was read written (6): 18. 30 x `wide` x `itemsize` a
    row; beta, the taps and the per-head vectors are left out."""
    return rows * wide * itemsize * 30
