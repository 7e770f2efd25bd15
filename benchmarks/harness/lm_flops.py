"""Operations the GLM-4.7-Flash share needs, from its shapes alone:
multiply-accumulates of the forward pass's matrix products by part (norms,
softmax, activations, the rotary turn and the embedding lookup left out, as
`flops.py` leaves them out). A training step needs the forward pass once and
twice that for the backward pass: FLOP = MACs x 2 x 3. Nothing recomputed
counts (the per-block and per-query-block rematerialisation is the program's
choice), the causal core counts the S(S+1)/2 pairs that exist, and the routed
experts count the (token, expert) slots the step's own counter saw, not the
worst-case rows of the dispatch buffer.
"""
from __future__ import annotations


def forward_macs(sizes: dict, seq_len: int, sequences: int, local_slots: float) -> dict:
    """part -> MACs of one step's forward pass over `sequences` x `seq_len` tokens; `local_slots` is the
    step's `moe.local_slots` (all expert layers, the MTP module's among them)."""
    d, heads = sizes['hidden_size'], sizes['num_attention_heads']
    qk = sizes['qk_nope_head_dim'] + sizes['qk_rope_head_dim']
    tokens = seq_len * sequences
    mtp = sizes['num_nextn_predict_layers']
    layers = sizes['num_hidden_layers'] + mtp                       # every layer has the attention
    dense = sizes['first_k_dense_replace']
    moe_layers = layers - dense
    expert = 3 * d * sizes['moe_intermediate_size']
    mla = (d * sizes['q_lora_rank'] + sizes['q_lora_rank'] * heads * qk
           + d * (sizes['kv_lora_rank'] + sizes['qk_rope_head_dim'])
           + sizes['kv_lora_rank'] * heads * (sizes['qk_nope_head_dim'] + sizes['v_head_dim'])
           + heads * sizes['v_head_dim'] * d)
    pairs = seq_len * (seq_len + 1) // 2 * sequences                # (query, key) pairs the causal mask leaves
    return {
        'mla_proj': tokens * layers * mla,
        'mla_core': pairs * layers * heads * (qk + sizes['v_head_dim']),
        'dense_ffn': tokens * dense * 3 * d * sizes['intermediate_size'],
        'moe_route': tokens * moe_layers * d * sizes['n_routed_experts'],
        'moe_shared': tokens * moe_layers * expert * sizes['n_shared_experts'],
        'moe_experts': local_slots * expert,
        'mtp_proj': tokens * mtp * 2 * d * d,
        'head': tokens * (1 + mtp) * d * sizes['vocab_held'],
    }


def train_flops(macs) -> float:
    """FLOP of a training step from forward MACs (a dict of parts or a number)."""
    return (sum(macs.values()) if isinstance(macs, dict) else macs) * 2 * 3
