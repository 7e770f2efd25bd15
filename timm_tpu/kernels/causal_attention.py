"""Causal flash attention on TPU for long token sequences: softmax(q k^T *
scale + causal mask) v without the (heads, S, S) scores, forward and backward.

The Pallas kernel is JAX's own splash attention
(`jax.experimental.pallas.ops.tpu.splash_attention`: blocked online softmax in
float32, causally dead blocks skipped, Pallas backward kernels for dq and
dk/dv); this module wraps it for (B, H, S, D) tensors, says at which shapes it
applies, and registers it. It is on `layers/latent_attention.py`'s default
path wherever `causal_flash_supported`; other shapes (the CPU tests' toy
sizes) take that module's XLA query-block path, which is also the registry's
reference.

Why a kernel here: at GLM-4.7-Flash's 2 x 20 heads x 8192 positions x 256 the
XLA path's masked row-maximum fusion runs at ~5 GB/s on a v5e and one layer
costs 578 ms forward and backward; with this kernel 64.5 ms (my chip runs,
PR 26; PERF.md section 6).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

BLOCK = 1024           # query and key/value block; a sequence shorter than it is one block
BLOCK_COMPUTE = 512    # key/value columns a kernel step multiplies at once
RESIDUALS = 'mla_core_out'   # checkpoint_name of the kernel's output and log-sum-exp, for a remat policy


def causal_flash_supported(q, k, v) -> bool:
    """Shapes the kernel takes: (B, H, S, D) with one S and one D for q, k and
    v, D a multiple of the 128 lanes, S a multiple of its block."""
    if q.ndim != 4 or not (q.shape == k.shape == v.shape):
        return False
    S, D = q.shape[2], q.shape[3]
    return D % 128 == 0 and S >= 256 and S % min(BLOCK, S) == 0 and min(BLOCK, S) % 128 == 0


def _kernel(heads: int, seq: int, interpret: bool):
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as sk
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as sm
    block, compute = min(BLOCK, seq), min(BLOCK_COMPUTE, seq)
    sizes = sk.BlockSizes(block_q=block, block_kv=block, block_kv_compute=compute,
                          block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=compute,
                          block_q_dq=block, block_kv_dq=block)
    mask = sm.MultiHeadMask([sm.CausalMask((seq, seq))] * heads)
    return sk.make_splash_mha(mask, block_sizes=sizes, head_shards=1, q_seq_shards=1,
                              residual_checkpoint_name=RESIDUALS, interpret=interpret)


def causal_flash_attention(q, k, v, scale: float):
    """(B, H, S, D) -> (B, H, S, D), causal over S; softmax in float32 inside the kernel."""
    if not causal_flash_supported(q, k, v):
        raise ValueError(f'causal_flash_attention does not take q {q.shape} k {k.shape} v {v.shape}')
    kernel = _kernel(q.shape[1], q.shape[2], jax.default_backend() != 'tpu')   # CPU tests run it interpreted
    return jax.vmap(kernel)(q * jnp.asarray(scale, q.dtype), k, v)


# ---------------------------------------------------------------------------
# registry entry


def _registry_reference(q, k, v):
    from ..layers.latent_attention import causal_attention
    return causal_attention(q, k, v, q.shape[-1] ** -0.5)


def _registry_kernel(q, k, v):
    return causal_flash_attention(q, k, v, q.shape[-1] ** -0.5)


def _registry_inputs(seed: int = 0, batch: int = 1, heads: int = 2, seq: int = 256, head_dim: int = 128,
                     dtype: str = 'float32'):
    import numpy as np
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.standard_normal((batch, heads, seq, head_dim)) * 0.5, dtype) for _ in range(3))
    return dict(q=q, k=k, v=v)


def _register():
    from .registry import KernelCase, KernelSpec, register
    register(KernelSpec(
        name='causal_flash_attention',
        module=__name__,
        regime='causal self-attention over thousands of positions with wide heads (latent attention at '
               'S = 8192, D = 256): the XLA path materialises (heads, block, S) float32 scores per query block',
        gate='beat the XLA query-block path at S >= 2048 on TPU or be deleted (v5e, one MLA layer forward and '
             'backward at 2 x 20 x 8192 x 256: 64.5 ms against 578 ms, PR 26)',
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
        ),
        backends=('tpu',),
    ))


_register()
