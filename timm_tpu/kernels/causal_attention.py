"""Causal flash attention on TPU for long token sequences: softmax(q k^T *
scale + mask) v without the (heads, S, S) scores, forward and backward. The
mask is causal, or causal AND within a window of the last `window` positions
(key j is seen by query i when j <= i and i - j < window), or the
block-diffusion mask over a noised copy beside the clean sequence
(`block_diffusion_seen`), or the chunk-window mask of a chunk-pooled linear
attention (`chunk_window_seen`: S / chunk summary keys before the S single
ones; a query sees the single keys of its own window causally and the
summaries of every earlier window); the query heads may be a multiple of the
key/value heads (grouped-query attention: query head g reads key/value head g // group).

The Pallas kernel is JAX's own splash attention
(`jax.experimental.pallas.ops.tpu.splash_attention`: blocked online softmax in
float32, blocks the mask leaves empty skipped forward and backward, Pallas
backward kernels for dq and dk/dv); this module wraps it for (B, H, S, D)
tensors, says at which shapes it applies, and registers it. With grouped heads
it runs splash attention's multi-query form once a key/value head, so K and V
are never repeated to the query heads in memory; the window is the kernel's own
mask (`LocalMask`), so the blocks outside it are skipped, not multiplied and
masked. It is on the default path of `layers/latent_attention.py` and
`layers/grouped_attention.py` wherever `causal_flash_supported`; other shapes
(the CPU tests' toy sizes) take those modules' XLA query-block paths, which are
also the registry's reference.

Why a kernel here: at GLM-4.7-Flash's 2 x 20 heads x 8192 positions x 256 the
XLA path's masked row-maximum fusion runs at ~5 GB/s on a v5e and one layer
costs 578 ms forward and backward; with this kernel 64.5 ms (my chip runs,
PR 26; PERF.md section 6).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

BLOCK = 1024           # query and key/value block; a sequence shorter than it is one block
BLOCK_COMPUTE = 512    # key/value columns a kernel step multiplies at once
RESIDUALS = 'mla_core_out'   # checkpoint_name of the kernel's output and log-sum-exp, for a remat policy


def _block(seq: int, chunk_window=None) -> int:
    """The kernel's query and key/value block for `seq` positions: `BLOCK`, a shorter sequence as one block;
    under the chunk-window mask also no longer than the seq // chunk summaries, which then fill whole blocks."""
    return min(BLOCK, seq if chunk_window is None else seq // chunk_window[1])


def block_diffusion_seen(q_ids, kv_ids, length: int, block: int):
    """The block-diffusion mask (BD3-LM arXiv:2503.09573) over 2 x `length` rows, `length` noised ones and then
    the `length` clean ones; row r carries position r mod length, in block (r mod length) // block. A noised
    query sees the noised keys of its own block and the clean keys of EARLIER blocks; a clean query sees the
    clean keys of its own and earlier blocks and nothing noised. Written with operators alone: splash
    attention calls it on NumPy index grids when it builds its block map and on the kernel's own index tiles."""
    q_clean, k_clean = q_ids >= length, kv_ids >= length
    bq, bk = (q_ids - q_clean * length) // block, (kv_ids - k_clean * length) // block
    return (k_clean & ((bk < bq) | (q_clean & (bk == bq)))) | (~q_clean & ~k_clean & (bk == bq))


def chunk_window_seen(q_ids, kv_ids, length: int, window: int, chunk: int):
    """The chunk-window mask of a chunk-pooled linear attention (EVA, arXiv:2302.04542, in the deterministic form
    with one softmax over both kinds of key): `length` queries on `length // chunk` summary keys, chunk j's at
    index j, and then the `length` single keys, position t's at index length // chunk + t. Query i, in window
    i // window, sees the single keys of its OWN window up to itself and the summaries of every chunk of every
    EARLIER window, none of its own. Written with operators alone (splash attention calls it on NumPy index grids
    when it builds its block map and on the kernel's own index tiles), and with the query's window taken by a bit
    mask and a shift where `window` and `chunk` are powers of two: the kernel evaluates this on every index tile it
    visits, and a division there costs more than the tile's products (PERF.md section 6, PR 37)."""
    summaries = length // chunk
    if window & (window - 1) == 0 and chunk & (chunk - 1) == 0:
        first = q_ids & -window                                     # the first position of the query's window
        before = first >> (chunk.bit_length() - 1)                  # summaries of the windows before it
    else:
        first = q_ids // window * window
        before = first // chunk
    t = kv_ids - summaries
    return ((t >= first) & (t <= q_ids)) | (kv_ids < before)


def causal_flash_supported(q, k, v, window=None, block_diffusion=None, chunk_window=None) -> bool:
    """Shapes the kernel takes: q (B, H, S, D), k and v (B, H_kv, S, D) with one S and one D, H a multiple of
    H_kv, D a multiple of the 128 lanes or, under the plain causal mask alone, half a lane tile (64: LFM2's heads;
    the kernel's blocks then span the whole of D, and its softmax state stays 128 lanes wide), S a multiple of its
    block; a window of at least one position. With
    `block_diffusion` (the block length) k and v hold 2 L rows, L noised and L clean, and q all of them or the
    L noised ones alone (a last layer's); L is then what the block has to divide. With `chunk_window` (a window
    and a chunk length) k and v hold S // chunk summaries and then the S single keys; the window divides S, the
    chunk the window, and the block (`_block`: the summaries' count where that is under `BLOCK`) both kinds."""
    if q.ndim != 4 or k.ndim != 4 or k.shape != v.shape or (window is not None and window < 1):
        return False
    (B, H, S, D), H_kv = q.shape, k.shape[1]
    if chunk_window is not None:
        (win, chunk), block = chunk_window, _block(S, chunk_window)
        if window is not None or block_diffusion is not None or win < 1 or chunk < 1 or S % win or win % chunk \
                or k.shape != (B, H_kv, S // chunk + S, D) or H % H_kv:
            return False
        return D % 128 == 0 and block % 128 == 0 and (S // chunk) % block == 0 and S % block == 0
    if block_diffusion is not None:
        L = k.shape[2] // 2
        if window is not None or block_diffusion < 1 or k.shape[2] != 2 * L or L % block_diffusion or S not in (L, 2 * L):
            return False
        S = L
    elif k.shape[2] != S:
        return False
    if k.shape != (B, H_kv, k.shape[2], D) or H % H_kv:
        return False
    lanes = D % 128 == 0 or (D == 64 and window is None and block_diffusion is None)
    return lanes and S >= 256 and S % min(BLOCK, S) == 0 and min(BLOCK, S) % 128 == 0


def _block_diffusion_seen_coded(code, kv_ids, length: int, block: int):
    """`block_diffusion_seen` for query rows that come as a code instead of an index: a noised row's code is the
    first position t of its block, a clean row's -(t + block). Then the row sees the noised keys [t, t + block)
    (none for a clean row: code + block <= 0) and the clean keys [L, L + |code|). Two range tests in signed
    compares: the kernel evaluates this on every index tile it visits, where the integer divisions of the
    plain form cost more than the tile's products (v5e, one layer forward 18.9 ms with the divisions, 11.8 ms
    with these compares: PERF.md section 6, PR 37), and an unsigned compare, one test a range, does not wrap in
    the compiled kernel as it does in NumPy and under the interpreter (the chip's `correct` found that)."""
    return ((kv_ids >= code) & (kv_ids < code + block)) | ((kv_ids >= length) & (kv_ids < length + abs(code)))


@functools.lru_cache(maxsize=None)
def _block_diffusion_mask(rows: int, length: int, block: int):
    """`block_diffusion_seen` as splash attention's computable mask: `rows` queries (2 x length, or the
    `length` noised ones alone) on 2 x length keys. The kernel hands the mask function a query row's entry of
    `q_sequence`, which here holds the row's code (`_block_diffusion_seen_coded`), not its index."""
    import numpy as np
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as sm

    class BlockDiffusionMask(sm._ComputableMask):
        def __init__(self):
            super().__init__(shape=(rows, 2 * length),
                             mask_function=lambda code, kv_ids: _block_diffusion_seen_coded(code, kv_ids, length, block))
            row = np.arange(rows)
            start = row % length // block * block
            self.q_sequence = np.where(row < length, start, -(start + block)).astype(np.int32)

        def __eq__(self, other):
            return type(other) is type(self)        # one class a (rows, length, block): the cache above

        def __hash__(self):
            return hash((type(self), self.shape, block))

    return BlockDiffusionMask()


@functools.lru_cache(maxsize=None)
def _chunk_window_mask(length: int, window: int, chunk: int):
    """`chunk_window_seen` as splash attention's computable mask: `length` queries on length // chunk + length
    keys; a query row's entry of `q_sequence` is its index."""
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as sm

    class ChunkWindowMask(sm._ComputableMask):
        def __init__(self):
            super().__init__(shape=(length, length // chunk + length),
                             mask_function=lambda q_ids, kv_ids: chunk_window_seen(q_ids, kv_ids, length, window, chunk))

        def __eq__(self, other):
            return type(other) is type(self)        # one class a (length, window, chunk): the cache above

        def __hash__(self):
            return hash((type(self), self.shape, window, chunk))

    return ChunkWindowMask()


def _kernel(heads: int, seq: int, interpret: bool, window=None, grouped: bool = False, block_diffusion=None, rows=None,
            chunk_window=None):
    """The splash kernel over `heads` query heads: one key/value head a query head, or with `grouped` one
    key/value head for all of them (the multi-query form). With `block_diffusion` `seq` is L, the keys are
    2 L and the queries `rows`; with `chunk_window` the keys are seq // chunk summaries and then the seq single ones."""
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as sk
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as sm
    block = _block(seq, chunk_window)
    compute = min(BLOCK_COMPUTE, block)
    sizes = sk.BlockSizes(block_q=block, block_kv=block, block_kv_compute=compute,
                          block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=compute,
                          block_q_dq=block, block_kv_dq=block)
    if block_diffusion is not None:
        one = _block_diffusion_mask(rows, seq, block_diffusion)
    elif chunk_window is not None:
        one = _chunk_window_mask(seq, *chunk_window)
    else:
        one = sm.CausalMask((seq, seq)) if window is None or window >= seq else sm.LocalMask((seq, seq), (window - 1, 0), 0)
    make = sk.make_splash_mqa if grouped else sk.make_splash_mha
    return make(sm.MultiHeadMask([one] * heads), block_sizes=sizes, head_shards=1, q_seq_shards=1,
                residual_checkpoint_name=RESIDUALS, interpret=interpret)


def causal_flash_attention(q, k, v, scale: float, window=None, with_tiles: bool = False, block_diffusion=None,
                           chunk_window=None):
    """q (B, H, S, D), k, v (B, H_kv, S, D) -> (B, H, S, D), causal over S and, with `window`, within the last
    `window` positions; or, with `block_diffusion` (the block length), q over 2 L rows or the L noised ones on
    k, v (B, H_kv, 2 L, D) under `block_diffusion_seen`; or, with `chunk_window` (a window and a chunk length), q
    on k, v (B, H_kv, S // chunk + S, D), summaries first, under `chunk_window_seen`: ONE softmax over both kinds
    of key, and the gradient reaches the summaries as it reaches any key. Softmax in float32 inside the kernel. `with_tiles`
    also returns how many (query block, key block) tiles of one sequence hold an unmasked pair, read from the
    kernel's own forward block map: the tiles it multiplies, the rest it skips."""
    if not causal_flash_supported(q, k, v, window, block_diffusion, chunk_window):
        raise ValueError(f'causal_flash_attention does not take q {q.shape} k {k.shape} v {v.shape} window {window} '
                         f'block_diffusion {block_diffusion} chunk_window {chunk_window}')
    (B, H, S, D), (H_kv, S_kv) = q.shape, k.shape[1:3]
    interpret = jax.default_backend() != 'tpu'                                  # CPU tests run it interpreted
    q = q * jnp.asarray(scale, q.dtype)
    if chunk_window is not None:
        mask = dict(chunk_window=tuple(chunk_window))
    else:
        mask = dict(window=window) if block_diffusion is None else dict(block_diffusion=block_diffusion, rows=S)
    length = S if block_diffusion is None else S_kv // 2
    if H == H_kv:
        kernel = _kernel(H, length, interpret, **mask)
        out = jax.vmap(kernel)(q, k, v)
    else:
        # one multi-query call a key/value head: K and V stay at H_kv heads in memory
        kernel = _kernel(H // H_kv, length, interpret, grouped=True, **mask)
        out = jax.vmap(kernel)(q.reshape(B * H_kv, H // H_kv, S, D), k.reshape(B * H_kv, S_kv, D),
                               v.reshape(B * H_kv, S_kv, D)).reshape(B, H, S, D)
    return (out, _tiles(length, **mask)) if with_tiles else out


@functools.lru_cache(maxsize=None)
def _tiles(seq: int, window=None, block_diffusion=None, rows=None, chunk_window=None) -> int:
    """(query block, key block) tiles with an unmasked pair in the forward block map of the kernel `_kernel`
    builds for this length and mask (one head's: the heads' masks are alike). Built eagerly: inside a
    trace the kernel's own copy of the map is a traced constant."""
    import numpy as np
    with jax.ensure_compile_time_eval():
        kernel = _kernel(1, seq, True, window, block_diffusion=block_diffusion, rows=rows, chunk_window=chunk_window)
        block_map = np.asarray(kernel.fwd_mask_info.block_mask)     # (1, query blocks, key blocks visited)
    return int((block_map[0] != 0).sum())


# ---------------------------------------------------------------------------
# registry entry


def _registry_reference(q, k, v, window=None, block_diffusion=None, chunk_window=None):
    from ..layers.chunked_linear_attention import chunk_window_attention
    from ..layers.grouped_attention import grouped_block_diffusion_attention, grouped_causal_attention
    from ..layers.latent_attention import causal_attention
    if chunk_window is not None:
        return chunk_window_attention(q, k, v, q.shape[-1] ** -0.5, *chunk_window)
    if block_diffusion is not None:
        return grouped_block_diffusion_attention(q, k, v, q.shape[-1] ** -0.5, block_diffusion)
    if window is None and q.shape == k.shape:
        return causal_attention(q, k, v, q.shape[-1] ** -0.5)
    return grouped_causal_attention(q, k, v, q.shape[-1] ** -0.5, window)


def _registry_kernel(q, k, v, window=None, block_diffusion=None, chunk_window=None):
    return causal_flash_attention(q, k, v, q.shape[-1] ** -0.5, window, block_diffusion=block_diffusion,
                                  chunk_window=chunk_window)


def _registry_inputs(seed: int = 0, batch: int = 1, heads: int = 2, seq: int = 256, head_dim: int = 128,
                     dtype: str = 'float32', kv_heads: int = None, summaries: int = 0):
    """`summaries` more key/value rows than queries (the chunk-window mask's summary keys, before the single ones)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.standard_normal((batch, h, rows, head_dim)) * 0.5, dtype)
               for h, rows in ((heads, seq), (kv_heads or heads, summaries + seq), (kv_heads or heads, summaries + seq)))
    return dict(q=q, k=k, v=v)


def _register():
    from .registry import KernelCase, KernelSpec, register
    register(KernelSpec(
        name='causal_flash_attention',
        module=__name__,
        parity_tol=2e-2,
        kernel_fn=_registry_kernel,
        reference_fn=_registry_reference,
        make_inputs=_registry_inputs,
        cases=(
            KernelCase(
                name='causal_s8192_d256',
                dry=dict(batch=1, heads=2, seq=256, head_dim=128),
                live=dict(batch=2, heads=20, seq=8192, head_dim=256, dtype='bfloat16'),
                desc='GLM-4.7-Flash latent attention, 2 sequences of 8192',
            ),
            KernelCase(
                name='gqa_full_s16384_d128',
                dry=dict(batch=1, heads=4, kv_heads=2, seq=256, head_dim=128),
                live=dict(batch=1, heads=28, kv_heads=4, seq=16384, head_dim=128, dtype='bfloat16'),
                desc='SmallThinker-21BA3B full (position-free) layer: 28 query heads on 4 key/value heads, 16384 '
                     'positions; v5e, one layer: forward 15.5 ms against the XLA query-block path\'s 88.1, forward and '
                     'backward 60.3 against 230.3 (PR 31)',
            ),
            KernelCase(
                name='gqa_full_s8192_d64',
                dry=dict(batch=1, heads=8, kv_heads=2, seq=256, head_dim=64),
                live=dict(batch=4, heads=32, kv_heads=8, seq=8192, head_dim=64, dtype='bfloat16'),
                desc='LFM2-8B-A1B attention layer: 32 query heads on 8 key/value heads of width 64 (half a lane tile), '
                     '4 sequences of 8192; v5e, one layer: forward 19.7 ms against the XLA query-block path\'s 777.6, forward and '
                     'backward 76.2 against 949.5; with q, k and v padded to 128 inside a wrapper 20.5 / 76.9: the native '
                     'width costs what the padded one does in the products and moves half the bytes (PR 43)',
            ),
            KernelCase(
                name='mqa_full_s8192_d128',
                dry=dict(batch=1, heads=8, kv_heads=1, seq=256, head_dim=128),
                live=dict(batch=1, heads=8, kv_heads=1, seq=8192, head_dim=128, dtype='bfloat16'),
                desc='Solar-Open2-250B gated attention layer, one chip\'s 8 of 64 query heads on 1 of 8 key/value heads: '
                     'the multi-query form once, the plain causal mask, no positions (PR 47)',
            ),
            KernelCase(
                name='gqa_window4096_s16384_d128',
                dry=dict(batch=1, heads=2, kv_heads=1, seq=5120, head_dim=128),
                live=dict(batch=1, heads=28, kv_heads=4, seq=16384, head_dim=128, dtype='bfloat16'),
                statics=dict(window=4096),
                desc='SmallThinker-21BA3B window layer: the same heads, keys within 4096 positions; v5e, one layer: '
                     'forward 8.0 ms against 46.8 for the XLA path that slices keys to the window, forward and backward '
                     '31.1 against 123.2 (PR 31)',
            ),
            KernelCase(
                name='gqa_block_diffusion4_s16384_d128',
                dry=dict(batch=1, heads=4, kv_heads=2, seq=512, head_dim=128),
                live=dict(batch=1, heads=32, kv_heads=4, seq=16384, head_dim=128, dtype='bfloat16'),
                statics=dict(block_diffusion=4),
                desc='SDAR-30B-A3B block-diffusion layer: 32 query heads on 4 key/value heads over 8192 noised rows '
                     'beside 8192 clean ones, blocks of 4, 80 of 256 tiles visited; v5e, one layer: forward 11.8 ms '
                     'against the XLA query-block path\'s 61.1, forward and backward 47.9 against 219.7; a last layer\'s '
                     'noised queries alone (44 tiles) 6.6 / 26.5 against 34.1 / 120.0 (PR 37)',
            ),
            KernelCase(
                name='chunk_window2048_16_s16384_d128',
                dry=dict(batch=1, heads=1, seq=4096, summaries=256, head_dim=128),
                live=dict(batch=1, heads=16, seq=16384, summaries=1024, head_dim=128, dtype='bfloat16'),
                statics=dict(chunk_window=(2048, 16)),
                desc='EvaByte chunk-pooled layer, one chip\'s 16 of 32 heads: 16384 queries on 1024 chunk summaries and '
                     'then the 16384 single keys, windows of 2048, 38 of 16 x 17 tiles visited (the dry case runs two '
                     'windows in blocks of 256); v5e, one layer: forward 2.50 ms against the XLA query-block path\'s 9.93, forward and '
                     'backward 11.00 against 30.34 (PR 41)',
            ),
        ),
    ))


_register()
