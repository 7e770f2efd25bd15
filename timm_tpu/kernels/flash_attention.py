"""Pallas TPU flash attention.

Dispatch status (PERF.md "Kernel portfolio & win-or-delete harness",
originally VERDICT r3 weak #4): the kernel is **opt-in only** —
`TIMM_TPU_PALLAS_ATTN=1` — because the plain einsum+softmax graph that XLA
fuses beat it at every unmasked image-model shape measured on v5e (ViT-B/16
train: 867 einsum vs 786 XLA-fused vs 573 Pallas img/s/chip). The deletion
gate — **win at masked N≥576** (NaFlex key-padding shapes, where the XLA
path must materialize a masked N² fp32 tensor this kernel never builds)
**or be deleted** — is no longer prose: it is the registry entry at the
bottom of this file, whose masked 576/784/1024 regime cases
`harness.run_kernel_ab` times against the `_sdpa` reference to emit the
keep/delete verdict. The tile-aligned token-padding path (vision_transformer.py
`pad_tokens_to`) threads exactly that key-padding mask here, which is the
prerequisite for running the gate experiment on live hardware.

Forward: blocked online-softmax kernel — Q blocks on the grid, KV chunks in a
fori_loop, running (max, denom, acc) carried functionally. Supports an
optional *key-padding* bool mask (the NaFlex case, reference
naflexvit.py:972-1040): (B, N) or (B, 1, 1, N), True = valid key. Any other
mask form (additive float masks, per-query 2D attention masks) raises — the
kernel would silently ignore the non-key-padding structure otherwise; those
forms stay on the XLA path in timm_tpu/layers/attention.py.

Backward: custom_vjp recomputes attention with plain XLA ops — exact same
math, N x N materialized only in the bwd pass (fine at image-model sequence
lengths); the fwd pass never materializes the score matrix.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _supported_backend() -> bool:
    return jax.default_backend() == 'tpu'


def flash_attention_supported(q, k, v, mask=None) -> bool:
    """Gate for the dispatch in layers/attention.py.

    Benchmarked on v5e: plain einsum+softmax (which XLA fuses) is the default
    for N<=1024 and jax.nn.dot_product_attention above that — both beat this
    kernel at every unmasked image-model shape tested (ViT-B/16 train: 867
    einsum vs 786 XLA-fused vs 573 Pallas img/s/chip). Recorded decision
    (PERF.md): the kernel stays explicit opt-in (TIMM_TPU_PALLAS_ATTN=1);
    the keep-or-delete experiment is masked N≥576 (NaFlex / token-padding
    key-padding masks) on live hardware — if it does not win there, it is
    deleted.
    """
    import os
    if os.environ.get('TIMM_TPU_PALLAS_ATTN', '0') != '1':
        return False
    if not _supported_backend():
        return False
    if q.ndim != 4:
        return False
    B, H, N, D = q.shape
    if D > 256 or k.shape != q.shape or v.shape != q.shape:
        return False  # MHA only (no MQA/GQA yet), head dim within one lane tile
    if N < 128:
        return False  # too small to beat the fused XLA path
    if mask is not None:
        if mask.dtype != jnp.bool_:
            return False
        # key-padding masks only: (B, N), (B, 1, 1, N)
        if mask.shape not in ((B, N), (B, 1, 1, N)):
            return False
    return True


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, *, scale: float, block_k: int, kv_len: int):
    # refs: q (BQ, D), k (N, D), v (N, D), mask (1, N) bool, o (BQ, D)
    # matmul inputs stay in the source dtype (bf16 on the fast path) with fp32
    # accumulation — halves MXU input bandwidth vs upcasting.
    q = q_ref[0, 0] * jnp.asarray(scale, q_ref.dtype)
    bq = q.shape[0]
    d = q.shape[1]
    num_k_blocks = kv_len // block_k

    def body(i, carry):
        acc, m_i, l_i = carry
        k_chunk = k_ref[0, 0, pl.ds(i * block_k, block_k), :]
        v_chunk = v_ref[0, 0, pl.ds(i * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_chunk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)  # (BQ, BK)
        kmask = mask_ref[0, 0, pl.ds(i * block_k, block_k)]
        s = jnp.where(kmask[None, :], s, -1e30)
        m_new = jnp.maximum(m_i, s.max(axis=-1))
        alpha = jnp.exp(m_i - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l_i * alpha + p.sum(axis=-1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(v_chunk.dtype), v_chunk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq,), -1e30, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc, m_i, l_i = jax.lax.fori_loop(0, num_k_blocks, body, (acc0, m0, l0))
    o_ref[0, 0] = (acc / jnp.maximum(l_i, 1e-30)[:, None]).astype(o_ref.dtype)


def _flash_fwd_impl(q, k, v, key_mask, scale: float, block_q: int = 256, block_k: int = 512):
    B, H, N, D = q.shape
    Nk = k.shape[2]
    block_q = min(block_q, max(128, 1 << (N - 1).bit_length()))
    block_q = min(block_q, N) if N % 128 == 0 else min(block_q, 256)
    block_k = min(block_k, max(128, 1 << (Nk - 1).bit_length()))

    # pad sequence dims to block multiples; padded keys masked out
    pad_q = (-N) % block_q
    pad_k = (-Nk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else v
    if key_mask is None:
        key_mask = jnp.ones((B, Nk), jnp.bool_)
    km = jnp.pad(key_mask, ((0, 0), (0, pad_k)), constant_values=False) if pad_k else key_mask
    km = km[:, None, :]  # (B, 1, Nkp) so the block's trailing dims satisfy tiling

    Np, Nkp = N + pad_q, Nk + pad_k
    grid = (B, H, Np // block_q)
    kernel = functools.partial(_fwd_kernel, scale=scale, block_k=block_k, kv_len=Nkp)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Nkp, D), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Nkp, D), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Nkp), lambda b, h, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Np, D), q.dtype),
        interpret=jax.default_backend() != 'tpu',  # CPU tests run the kernel interpreted
    )(qp, kp, vp, km)
    if pad_q:
        out = out[:, :, :N]
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _flash(q, k, v, key_mask, scale):
    return _flash_fwd_impl(q, k, v, key_mask, scale)


def _flash_fwd_rule(q, k, v, key_mask, scale):
    out = _flash_fwd_impl(q, k, v, key_mask, scale)
    return out, (q, k, v, key_mask)


def _flash_bwd_rule(scale, residuals, g):
    q, k, v, key_mask = residuals
    # exact recompute in fp32 via XLA (N x N lives only here)
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum('bhqd,bhkd->bhqk', qf, kf)
    if key_mask is not None:
        s = jnp.where(key_mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    gf = g.astype(jnp.float32)
    dv = jnp.einsum('bhqk,bhqd->bhkd', p, gf)
    dp = jnp.einsum('bhqd,bhkd->bhqk', gf, vf)
    ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
    dq = jnp.einsum('bhqk,bhkd->bhqd', ds, kf) * scale
    dk = jnp.einsum('bhqk,bhqd->bhkd', ds, qf)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), None


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, mask=None, scale: Optional[float] = None):
    """(B, H, N, D) fused attention with optional key-padding mask.

    `mask` must be a bool key-padding mask, (B, N) or (B, 1, 1, N) with
    True = valid key. Anything else raises: this kernel only applies
    key-padding structure, and silently flattening a full additive /
    per-query mask into it would produce wrong output.
    """
    scale = float(scale) if scale is not None else q.shape[-1] ** -0.5
    key_mask = None
    if mask is not None:
        B, _, N, _ = q.shape
        Nk = k.shape[2]
        if mask.dtype != jnp.bool_:
            raise ValueError(
                f'flash_attention only supports bool key-padding masks; got dtype {mask.dtype}. '
                'Additive float masks must use the XLA attention path '
                '(timm_tpu.layers.scaled_dot_product_attention with fused=False).')
        if mask.shape not in ((B, Nk), (B, 1, 1, Nk)):
            raise ValueError(
                f'flash_attention only supports key-padding masks of shape {(B, Nk)} or '
                f'{(B, 1, 1, Nk)}; got {mask.shape}. Per-query attention masks would be '
                'silently collapsed to their first query row — use the XLA path instead.')
        key_mask = mask[:, 0, 0, :] if mask.ndim == 4 else mask
    return _flash(q, k, v, key_mask, scale)


# ---------------------------------------------------------------------------
# registry entry: the masked-N>=576-or-delete gate as executable data


def _registry_reference(q, k, v, mask):
    from ..layers.attention import _sdpa
    return _sdpa(q, k, v, attn_mask=mask)


def _registry_kernel(q, k, v, mask):
    return flash_attention(q, k, v, mask=mask)


def _registry_inputs(seed: int = 0, batch: int = 2, heads: int = 2,
                     seq: int = 576, head_dim: int = 64,
                     valid_frac: float = 0.8, dtype: str = 'float32'):
    import numpy as np
    rng = np.random.default_rng(seed)
    shape = (batch, heads, seq, head_dim)
    q, k, v = (jnp.asarray(rng.standard_normal(shape) * 0.5, dtype)
               for _ in range(3))
    # NaFlex-style key padding: a varying valid prefix per batch row
    mask = np.zeros((batch, 1, 1, seq), bool)
    for i in range(batch):
        mask[i, ..., :max(1, int(seq * valid_frac) - 8 * i)] = True
    return dict(q=q, k=k, v=v, mask=jnp.asarray(mask))


def _register():
    from .registry import KernelCase, KernelSpec, register
    register(KernelSpec(
        name='flash_attention',
        module=__name__,
        regime='key-padding-masked attention at NaFlex packed lengths '
               '(N in {576, 784, 1024}, D<=256): the XLA path materializes '
               'a masked N^2 fp32 score tensor this kernel never builds',
        gate='win at masked N>=576 on TPU or be deleted (v5e already showed '
             'XLA winning every unmasked image-model shape)',
        parity_tol=2e-2,
        kernel_fn=_registry_kernel,
        reference_fn=_registry_reference,
        make_inputs=_registry_inputs,
        cases=(
            KernelCase(
                name='masked_n576',
                dry=dict(batch=2, heads=2, seq=576, head_dim=64),
                live=dict(batch=16, heads=12, seq=576, head_dim=64,
                          dtype='bfloat16'),
                desc='NaFlex 384px/16 packed bucket',
            ),
            KernelCase(
                name='masked_n784',
                dry=dict(batch=1, heads=2, seq=784, head_dim=64),
                live=dict(batch=16, heads=12, seq=784, head_dim=64,
                          dtype='bfloat16'),
                desc='NaFlex 448px/16 packed bucket',
            ),
            KernelCase(
                name='masked_n1024',
                dry=dict(batch=1, heads=1, seq=1024, head_dim=64),
                live=dict(batch=16, heads=12, seq=1024, head_dim=64,
                          dtype='bfloat16'),
                desc='NaFlex max packed bucket',
            ),
        ),
        backends=('tpu',),
    ))


_register()
