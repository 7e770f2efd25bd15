"""Short-sequence attention as one Pallas kernel pair on the qkv product's own layout.

At image-model lengths a whole image's scores fit in VMEM, so the core needs no online softmax and no
blocking over keys. The forward kernel reads the qkv product's output as it is, (B, N, 3 * H * D) (the free
reshape of (B, N, 3, H, D)), and writes (B, N, H * D), which the output product takes as it is: no head
transpose on either side, and no (B, H, N, N) array in HBM in either pass.

Blocking: a grid step holds `group` images (`_group`: the most that fit the VMEM budget, a divisor of B) and
loops over them; inside an image the heads are a static loop over 128-lane columns of the block. A head
narrower than 128 lanes shares its column with 128 / D - 1 others: the column is loaded once, the other heads'
lanes of q are zeroed (a 128-deep contraction costs the MXU what a D-deep one does), P V is taken over the
whole column and the head's own lanes selected from it. Nothing is shuffled across lanes. Rows are padded
to 16 (queries) and 128 (keys) by asking for a block larger than the array: what lies beyond row N is
unspecified on the way in, so every tile's rows >= N are zeroed and the scores' columns >= N masked, and is
dropped on the way out.

Forward, a head: q k^T with float32 accumulation (q scaled in its own dtype first, as `_sdpa` scales it), one
`where` for the padded or key-padded columns, softmax in float32 in ONE pass, exp(s - max) rounded to the
activations' dtype for P V, the row sum divided out of the (N, D) product, and the row's log-sum-exp kept.
Backward (`jax.custom_vjp`), same blocking: P from q, k and the log-sum-exp, delta = rowsum(dO * O),
dV = P^T dO, dP = dO V^T, dS = P (dP - delta), dQ = dS K scale, dK = dS^T Q scale, written as ONE
(B, N, 3 * H * D) gradient. The residuals are the qkv array (kept anyway), the output and the log-sum-exp.

A key-padding mask (bool, (B, N) or (B, 1, 1, N), True = valid key) is the same `where`. A row with no valid
key gives a finite mean over the padded keys, not `_sdpa`'s mean over all of them; no caller masks every key.

What it replaced: a one-head-a-step forward kernel over (B, H, N, D) with an XLA backward, opt-in, which lost
to XLA's plain path on the first chip runs. Measured on a v5e (PERF.md sections 5 and 6, PR 40): ViT-B/16's
train step, B 128, N 197, 12 x 64 heads, bfloat16, twelve layers: the core 32.5 ms a step on `_sdpa` -> 15.5
(backward 11.3, forward 4.2), the 13.6 ms of head transposes around it gone, the step 149.1 -> 116.5 ms,
855 -> 1094 img/s; masked N 576 / 784 / 1024 at batch 16, forward + backward a layer: 2.17 / 4.09 / 6.28 ms on
`_sdpa` -> 0.79 / 1.66 / 2.25.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MAX_SEQ = 1024                       # the plain path's own bound (layers/attention.py)
VMEM_BUDGET = 40 * 2 ** 20           # what `_group` fills with images: `_vmem_bytes`
VMEM_LIMIT = 100 * 2 ** 20           # the kernels' limit, of a v5e core's 128 MiB; a shape that needs more keeps `_sdpa`
MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _key_mask(mask, batch: int, seq: int):
    """A bool key-padding mask as (B, N), or None; anything else raises: the kernel applies key structure only."""
    if mask is None:
        return None
    if mask.dtype != jnp.bool_:
        raise ValueError(f'flash_attention only supports bool key-padding masks; got dtype {mask.dtype}. '
                         'Additive masks take the plain path (scaled_dot_product_attention with fused=False).')
    if mask.shape not in ((batch, seq), (batch, 1, 1, seq)):
        raise ValueError(f'flash_attention only supports key-padding masks of shape {(batch, seq)} or '
                         f'{(batch, 1, 1, seq)}; got {mask.shape}. A per-query mask takes the plain path.')
    return mask.reshape(batch, seq)


def packed_attention_supported(batch: int, seq: int, heads: int, head_dim: int, mask=None, itemsize: int = 4) -> bool:
    """Shapes and mask kinds the kernel pair takes, on any backend: N <= 1024, heads that fill whole 128-lane
    columns (D divides 128 with H * D a multiple of 128, or D a multiple of 128), no mask or a bool
    key-padding mask, (B, N) or (B, 1, 1, N), and one image's blocks and scores within `VMEM_LIMIT`."""
    if not 1 <= seq <= MAX_SEQ or heads < 1 or head_dim < 8:
        return False
    if head_dim % 128 and (128 % head_dim or (heads * head_dim) % 128):
        return False
    if _vmem_bytes(1, seq, heads, head_dim, itemsize, backward=True) > VMEM_LIMIT:
        return False
    return mask is None or (mask.dtype == jnp.bool_ and mask.shape in ((batch, seq), (batch, 1, 1, seq)))


def flash_attention_supported(batch: int, seq: int, heads: int, head_dim: int, mask=None, *,
                              dropout_p: float = 0.0, softmax_dtype=None, itemsize: int = 4) -> bool:
    """Whether a self-attention call takes the kernel pair: decided by what the call can see, with no switch
    of its own. `use_fused_attn()` (a TPU backend, not exporting; `set_fused_attn(True, experimental=True)`
    forces it elsewhere, interpreted), no attention dropout, the float32 softmax policy on the instance
    (`softmax_dtype`) and in the process, a global mesh that leaves heads whole (`_batch_axes`), and
    `packed_attention_supported`'s shapes (`itemsize`: the operands' bytes an element) and mask kinds."""
    from ..layers.config import softmax_dtype as process_softmax_dtype
    from ..layers.config import use_fused_attn
    if not use_fused_attn() or dropout_p > 0.0 or softmax_dtype is not None or process_softmax_dtype() is not None:
        return False
    return _batch_axes(batch) is not None and packed_attention_supported(batch, seq, heads, head_dim, mask, itemsize)


def _batch_axes(batch: int):
    """The global mesh's axes that split a call's batch: () on one device (or with no mesh), every axis of a
    mesh without a 'model' axis if their sizes' product divides the batch (the pair then runs under `shard_map`
    over them, a device its own images), None otherwise: heads split over 'model' keep `_sdpa`."""
    from ..parallel import peek_global_mesh
    mesh = peek_global_mesh()
    if mesh is None or mesh.size == 1:
        return ()
    if 'model' in mesh.axis_names or batch % mesh.size:
        return None
    return tuple(mesh.axis_names)


def _geometry(seq: int, heads: int, head_dim: int):
    """(query rows, key rows, column width, heads a column, columns)."""
    width = max(128, head_dim)
    return _round_up(seq, 16), _round_up(seq, 128), width, width // head_dim, heads * head_dim // width


def _vmem_bytes(group: int, seq: int, heads: int, head_dim: int, itemsize: int, backward: bool) -> int:
    """What a grid step of `group` images holds in VMEM, from above: its blocks twice (the pipeline's two
    buffers) and a float32 (query rows, key rows) array a head: the heads are unrolled and the compiler gives
    each its own (the forward kernel at N 1024, 16 heads, float32 needed 100.1 MiB, where this counts 97 for it
    and 129 for the backward one, which is what `packed_attention_supported` asks about)."""
    nq, nk, _, _, _ = _geometry(seq, heads, head_dim)
    c = heads * head_dim
    image = nk * 3 * c * itemsize + nq * c * itemsize + nq * 128 * -(-heads // 128) * 4
    if backward:
        image += nk * 3 * c * itemsize + nq * c * itemsize
    return 2 * group * image + heads * nq * nk * 4


def _group(batch: int, seq: int, heads: int, head_dim: int, itemsize: int, backward: bool) -> int:
    """Images a grid step: the largest divisor of B, at most 8, that `VMEM_BUDGET` holds (ViT-B's shape read the
    same time at 1, 2, 4 and 8 on a v5e: the step's overhead is hidden either way)."""
    fits = [g for g in range(1, min(8, batch) + 1)
            if batch % g == 0 and _vmem_bytes(g, seq, heads, head_dim, itemsize, backward) <= VMEM_BUDGET]
    return max(fits, default=1)


def _valid(n: int, rows: int, axis: int):
    shape = (rows, 1) if axis == 0 else (1, rows)
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis) < n


def _fwd_kernel(*refs, seq, heads, head_dim, scale, masked):
    qkv_ref, mask_ref, o_ref, lse_ref = refs if masked else (refs[0], None, *refs[1:])
    nq, nk, width, per_col, cols = _geometry(seq, heads, head_dim)
    c = heads * head_dim
    dtype = qkv_ref.dtype
    key_rows = _valid(seq, nk, 0)
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) // head_dim

    def image(i, carry):
        keep = mask_ref[i] != 0 if masked else _valid(seq, nk, 1)                  # (1, nk)
        for col in range(cols):
            at = col * width
            q2 = qkv_ref[i, :nq, at:at + width] * jnp.asarray(scale, dtype)
            k2 = qkv_ref[i, :, c + at:c + at + width]
            v2 = jnp.where(key_rows, qkv_ref[i, :, 2 * c + at:2 * c + at + width], 0)
            out2 = None
            for t in range(per_col):
                mine = lane_head == t
                qa = jnp.where(mine, q2, 0) if per_col > 1 else q2
                s = jax.lax.dot_general(qa, k2, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
                s = jnp.where(keep, s, MASKED)
                m = s.max(axis=1, keepdims=True)
                e = jnp.exp(s - m)
                l = e.sum(axis=1, keepdims=True)
                o = jnp.dot(e.astype(dtype), v2, preferred_element_type=jnp.float32) / l
                out2 = o if out2 is None else jnp.where(mine, o, out2)
                h = col * per_col + t
                lse_ref[i, :, h:h + 1] = m + jnp.log(l)
            o_ref[i, :, at:at + width] = out2.astype(dtype)
        return carry

    jax.lax.fori_loop(0, qkv_ref.shape[0], image, 0)


def _bwd_kernel(*refs, seq, heads, head_dim, scale, masked):
    qkv_ref, mask_ref, o_ref, lse_ref, do_ref, dqkv_ref = refs if masked else (refs[0], None, *refs[1:])
    nq, nk, width, per_col, cols = _geometry(seq, heads, head_dim)
    c = heads * head_dim
    dtype = qkv_ref.dtype
    key_rows, query_rows = _valid(seq, nk, 0), _valid(seq, nq, 0)
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) // head_dim
    nt, tn = (((1,), (1,)), ((), ())), (((0,), (0,)), ((), ()))

    def image(i, carry):
        keep = (mask_ref[i] != 0 if masked else _valid(seq, nk, 1)) & query_rows   # (nq, nk)
        for col in range(cols):
            at = col * width
            q2 = jnp.where(query_rows, qkv_ref[i, :nq, at:at + width], 0) * jnp.asarray(scale, dtype)
            k2 = jnp.where(key_rows, qkv_ref[i, :, c + at:c + at + width], 0)
            v2 = jnp.where(key_rows, qkv_ref[i, :, 2 * c + at:2 * c + at + width], 0)
            do2 = jnp.where(query_rows, do_ref[i, :, at:at + width], 0)
            od = jnp.where(query_rows, o_ref[i, :, at:at + width], 0).astype(jnp.float32) * do2.astype(jnp.float32)
            dq2 = dk2 = dv2 = None
            for t in range(per_col):
                mine = lane_head == t
                qa, doa, oda = (jnp.where(mine, x, 0) for x in (q2, do2, od)) if per_col > 1 else (q2, do2, od)
                h = col * per_col + t
                delta = oda.sum(axis=1, keepdims=True)
                s = jax.lax.dot_general(qa, k2, nt, preferred_element_type=jnp.float32)
                p = jnp.where(keep, jnp.exp(s - lse_ref[i, :, h:h + 1]), 0)
                dp = jax.lax.dot_general(doa, v2, nt, preferred_element_type=jnp.float32)
                ds = (p * (dp - delta)).astype(dtype)
                dv = jax.lax.dot_general(p.astype(dtype), do2, tn, preferred_element_type=jnp.float32)
                dk = jax.lax.dot_general(ds, q2, tn, preferred_element_type=jnp.float32)
                dq = jnp.dot(ds, k2, preferred_element_type=jnp.float32)
                dq2, dk2, dv2 = ((new if old is None else jnp.where(mine, new, old))
                                 for new, old in ((dq, dq2), (dk, dk2), (dv, dv2)))
            dqkv_ref[i, :nq, at:at + width] = (dq2 * scale).astype(dtype)
            dqkv_ref[i, :, c + at:c + at + width] = dk2.astype(dtype)
            dqkv_ref[i, :, 2 * c + at:2 * c + at + width] = dv2.astype(dtype)
        return carry

    jax.lax.fori_loop(0, qkv_ref.shape[0], image, 0)


@functools.partial(jax.jit, static_argnames=('kernel', 'backward', 'heads', 'scale', 'outs', 'interpret'))
def _call(kernel, backward, qkv, key_mask, more, heads, scale, outs, interpret):
    """One `pallas_call` over groups of images. `more`: the arrays beside qkv and the mask, all (B, N, ..);
    `outs`: (last dimension, dtype, padded rows) of each output. Under `jax.jit`, so that a model's layers of
    one shape share ONE trace and ONE lowering of each kernel: twelve ViT-B layers traced and lowered apart
    cost a step program's trace + lowering 3.3 s more here and ~9 s more on the chip's host, every run, cached
    executable or not (PERF.md section 6, PR 40)."""
    B, N, c3 = qkv.shape
    head_dim = c3 // 3 // heads
    nq, nk, _, _, _ = _geometry(N, heads, head_dim)
    group = _group(B, N, heads, head_dim, qkv.dtype.itemsize, backward)
    block = lambda rows, last: pl.BlockSpec((group, rows, last), lambda b: (b, 0, 0))  # noqa: E731
    args, in_specs = [qkv], [block(nk, c3)]
    if key_mask is not None:
        args.append(jnp.pad(key_mask.astype(jnp.int32), ((0, 0), (0, nk - N)))[:, None, :])
        in_specs.append(block(1, nk))
    args += more
    in_specs += [block(nq, a.shape[-1]) for a in more]
    kernel = functools.partial(kernel, seq=N, heads=heads, head_dim=head_dim, scale=scale, masked=key_mask is not None)
    return pl.pallas_call(
        kernel,
        grid=(B // group,),
        in_specs=in_specs,
        out_specs=[block(rows, last) for last, _, rows in outs],
        out_shape=[jax.ShapeDtypeStruct((B, N, last), dtype) for last, dtype, _ in outs],
        compiler_params=pltpu.CompilerParams(dimension_semantics=('parallel',), vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name='packed_attention_bwd' if backward else 'packed_attention_fwd',
    )(*args)


def _interpreted() -> bool:
    return jax.default_backend() != 'tpu'                                          # CPU tests run the kernels interpreted


def _forward(qkv, key_mask, heads, scale):
    B, N, c3 = qkv.shape
    nq, nk, _, _, _ = _geometry(N, heads, c3 // 3 // heads)
    return _call(_fwd_kernel, False, qkv, key_mask, [], heads, scale,
                 ((c3 // 3, qkv.dtype, nq), (128 * -(-heads // 128), jnp.float32, nq)), _interpreted())


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _attend(qkv, key_mask, heads, scale):
    return _forward(qkv, key_mask, heads, scale)[0]


def _attend_fwd(qkv, key_mask, heads, scale):
    out, lse = _forward(qkv, key_mask, heads, scale)
    return out, (qkv, key_mask, out, lse)


def _attend_bwd(heads, scale, residuals, g):
    qkv, key_mask, out, lse = residuals
    nk = _geometry(qkv.shape[1], heads, qkv.shape[2] // 3 // heads)[1]
    dqkv, = _call(_bwd_kernel, True, qkv, key_mask, [out, lse, g.astype(qkv.dtype)], heads, scale,
                  ((qkv.shape[2], qkv.dtype, nk),), _interpreted())
    return dqkv, None


_attend.defvjp(_attend_fwd, _attend_bwd)


def packed_attention(qkv, heads: int, mask=None, scale: Optional[float] = None):
    """qkv (B, N, 3 * H * D), the qkv product's output -> (B, N, H * D), the output product's input. Under a
    global mesh over several devices (data / fsdp axes: `_batch_axes`) each device runs the pair on its own images."""
    B, N, c3 = qkv.shape
    head_dim = c3 // 3 // heads
    if c3 != 3 * heads * head_dim or not packed_attention_supported(B, N, heads, head_dim, itemsize=qkv.dtype.itemsize):
        raise ValueError(f'packed_attention does not take qkv {qkv.shape} with {heads} heads')
    scale = float(scale) if scale is not None else head_dim ** -0.5
    key_mask, axes = _key_mask(mask, B, N), _batch_axes(B)
    if not axes:
        return _attend(qkv, key_mask, heads, scale)
    from ..parallel import peek_global_mesh
    split = jax.sharding.PartitionSpec(axes)
    return jax.shard_map(lambda x, m: _attend(x, m, heads, scale), mesh=peek_global_mesh(), in_specs=(split, split),
                         out_specs=split, check_vma=False)(qkv, key_mask)


def flash_attention(q, k, v, mask=None, scale: Optional[float] = None):
    """The (B, H, N, D) entry of the same pair, for callers that hold q, k, v apart: packs them onto the
    (B, N, 3 * H * D) layout (one copy, which XLA folds away where q, k, v were slices of such an array) and
    gives back (B, H, N, D). `mask`: a bool key-padding mask, (B, N) or (B, 1, 1, N), True = valid key."""
    B, H, N, D = q.shape
    if not (q.shape == k.shape == v.shape and q.dtype == k.dtype == v.dtype
            and packed_attention_supported(B, N, H, D, itemsize=q.dtype.itemsize)):
        raise ValueError(f'flash_attention does not take q {q.shape} k {k.shape} v {v.shape}')
    qkv = jnp.stack([q, k, v], axis=1).transpose(0, 3, 1, 2, 4).reshape(B, N, 3 * H * D)
    return packed_attention(qkv, H, mask, scale).reshape(B, N, H, D).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# registry entry


def _registry_reference(qkv, mask=None):
    from ..layers.attention import _sdpa
    B, N, _, H, D = qkv.shape
    q, k, v = qkv.transpose(2, 0, 3, 1, 4)
    return _sdpa(q, k, v, attn_mask=mask).transpose(0, 2, 1, 3).reshape(B, N, H * D)


def _registry_kernel(qkv, mask=None):
    B, N, _, H, D = qkv.shape
    return packed_attention(qkv.reshape(B, N, 3 * H * D), H, mask)


def _registry_inputs(seed: int = 0, batch: int = 2, heads: int = 2, seq: int = 197, head_dim: int = 64,
                     valid_frac: Optional[float] = None, dtype: str = 'float32'):
    """qkv as (B, N, 3, H, D), the free reshape of the qkv product's output (it carries H to both arms)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    inputs = dict(qkv=jnp.asarray(rng.standard_normal((batch, seq, 3, heads, head_dim)) * 0.5, dtype))
    if valid_frac is not None:
        # NaFlex-style key padding: a varying valid prefix per batch row
        mask = np.zeros((batch, 1, 1, seq), bool)
        for i in range(batch):
            mask[i, ..., :max(1, int(seq * valid_frac) - 8 * i)] = True
        inputs['mask'] = jnp.asarray(mask)
    return inputs


def _register():
    from .registry import KernelCase, KernelSpec, register
    masked = lambda seq, batch: KernelCase(  # noqa: E731
        name=f'masked_n{seq}',
        dry=dict(batch=batch, heads=2, seq=seq, head_dim=64, valid_frac=0.8),
        live=dict(batch=16, heads=12, seq=seq, head_dim=64, valid_frac=0.8, dtype='bfloat16'),
        desc='NaFlex packed bucket under its key-padding mask')
    register(KernelSpec(
        name='flash_attention',
        module=__name__,
        parity_tol=2e-2,
        kernel_fn=_registry_kernel,
        reference_fn=_registry_reference,
        make_inputs=_registry_inputs,
        cases=(
            KernelCase(
                name='vit_b16_n197',
                dry=dict(batch=3, heads=2, seq=197, head_dim=64),
                live=dict(batch=128, heads=12, seq=197, head_dim=64, dtype='bfloat16'),
                desc='ViT-B/16 at 224: the benchmark cell\'s shape, no mask'),
            masked(576, 2), masked(784, 1), masked(1024, 1),
        ),
    ))


_register()
