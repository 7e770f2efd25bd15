"""Operations and bytes the EvaByte share needs, from its shapes alone, as
`lm_flops.py` counts the GLM share's: multiply-accumulates of the forward
pass's matrix products by part (norms, both softmaxes, activations, the rotary
turn and the embedding lookup left out). A training step needs the forward pass
once and twice that for the backward pass: FLOP = MACs x 2 x 3. Nothing
recomputed counts, and nothing the mask excludes: the core counts the NEEDED
pairs of its two kinds, single keys of the query's own window (causal) and chunk
summaries of all earlier windows, whatever tiles a kernel visits to cover them.
The chunk summaries are the one part bound by memory, so they have a byte count
beside their (small) operation count. The model has no router: nothing here reads
a step counter.
"""
from __future__ import annotations

from .lm_flops import train_flops  # noqa: F401  the same x 2 x 3


def window_pairs(seq_len: int, window: int) -> int:
    """(query i, single key t) pairs with t in i's window and t <= i."""
    return seq_len // window * (window * (window + 1) // 2)


def summary_pairs(seq_len: int, window: int, chunk: int) -> int:
    """(query i, chunk j) pairs with chunk j in a window BEFORE i's: window w's queries see w * window / chunk."""
    windows = seq_len // window
    return window * (window // chunk) * (windows * (windows - 1) // 2)


def core_pairs(seq_len: int, window: int, chunk: int) -> int:
    return window_pairs(seq_len, window) + summary_pairs(seq_len, window, chunk)


def visited_tiles(seq_len: int, window: int, chunk: int, side: int):
    """(query block, key block) tiles of `side` x `side` that hold a needed pair, the summaries' blocks apart from
    the single keys'; None where `side` does not divide the window and the summaries into whole blocks."""
    if side < 1 or window % side or (seq_len // chunk) % side:
        return None
    blocks, windows = window // side, seq_len // window
    own = windows * blocks * (blocks + 1) // 2
    earlier = sum(blocks * -(-(w * (window // chunk)) // side) for w in range(windows))
    return own + earlier


def forward_macs(sizes: dict, seq_len: int, sequences: int) -> dict:
    """part -> MACs of one step's forward pass over `sequences` x `seq_len` positions."""
    d, held, hd = sizes['hidden_size'], sizes['heads_held'], sizes['head_dim']
    tokens, layers = seq_len * sequences, sizes['num_hidden_layers']
    pairs = core_pairs(seq_len, sizes['window_size'], sizes['chunk_size'])
    return {
        'attn_proj': tokens * layers * 4 * d * held * hd,
        'attn_summary': tokens * layers * held * 3 * hd,                 # k . phi, and the two pooled sums
        'attn_core': pairs * sequences * layers * held * 2 * hd,         # q k^T and p v, every head held
        'ffn': tokens * layers * 3 * d * sizes['intermediate_size'],
        'head': tokens * d * sizes['num_pred_heads'] * sizes['vocab_size'],
    }


def summary_bytes(sizes: dict, seq_len: int, sequences: int, itemsize: int = 2) -> int:
    """Bytes the chunk summaries NEED to move in a training step, all layers: forward, k and v read once and the
    seq_len / chunk summary keys and values written; backward, the same tensors read again with the summaries'
    gradients, and the gradients of k and v written. Operands in the compute dtype (`itemsize` 2: bfloat16); the
    two learned vectors a head are a few KB and left out."""
    kv = 2 * sequences * sizes['heads_held'] * seq_len * sizes['head_dim'] * itemsize
    pooled = kv // sizes['chunk_size']
    forward = kv + pooled
    backward = kv + pooled + pooled + kv       # k, v and the summaries read again, the summaries' gradients read, dk and dv written
    return sizes['num_hidden_layers'] * (forward + backward)
