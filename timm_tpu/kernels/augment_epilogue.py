"""Fused device-augment epilogue (Pallas).

The PR-9 `DeviceAugment` program (data/device_augment.py
`augment_image_batch`) is pure streaming: uint8 -> [0,1] float -> erase ->
mixup -> normalize -> cast. XLA executes it as several HBM passes over the
(B, H, W, C) canvas — the float upcast, each erase `where`, the lam blend +
cutmix paste (which also re-reads the flipped batch), and the normalize each
stream the full image. This kernel runs the whole epilogue per image in one
grid step: block b DMAs its own uint8 row AND the batch-flipped row (the
mixup partner, via a reversed index map — the flipped row is erased with
*its* boxes, exactly like the reference where `x_flip = erased[::-1]`),
applies erase/mix/normalize in VMEM, and writes the normalized out_dtype
image once.

Layout: (B, H, W, C) is viewed as (B, H, W*C) so the minor axis is dense;
a lane's pixel-x coordinate is `lane // C`, and the per-channel mean/std/
erase-fill vectors are baked in as W-tiled compile-time rows. Identity is
encoded in values (lam=1, zero boxes) per the device_augment convention, so
one compiled program serves mixup/cutmix/erase/no-op batches alike.

Scope (the declared regime, see the registry entry): 'const' erase mode
only. 'pixel' mode needs a full random canvas (not one-pass by nature) and
'rand' carries per-box fills; both fall back to the XLA program in
`augment_image_batch_fused`, as does any future mask form the kernel does
not mirror. The numpy oracle `augment_image_batch_np` remains the source of
truth; the XLA program is the A/B reference arm.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .registry import KernelCase, KernelSpec, register

__all__ = ['augment_epilogue', 'augment_image_batch_fused',
           'augment_epilogue_supported']


def _interpret() -> bool:
    return jax.default_backend() != 'tpu'


def augment_epilogue_supported(batch, re_mode: str = 'const') -> bool:
    """The fused kernel mirrors the 'const'-erase epilogue only; 'pixel'
    noise canvases and 'rand' per-box fills stay on the XLA program."""
    return re_mode == 'const' and 'erase_fill' not in batch


def _epilogue_kernel(lam_ref, cut_ref, bbox_ref, eb_ref,
                     mean_ref, std_ref, fill_ref,
                     img_ref, flip_ref, o_ref, *,
                     channels: int, erase_k: int):
    # blocks: img/flip/o (1, H, W*C); the per-image scalars are whole flat
    # arrays in SMEM indexed by the grid position (the TPU lowering takes no
    # (1, 1) SMEM blocks); mean/std/fill are W-tiled (1, W*C) rows shared by
    # every grid step.
    i = pl.program_id(0)
    j = pl.num_programs(0) - 1 - i  # the mixup partner's row
    h, wc = o_ref.shape[1], o_ref.shape[2]
    # via int32: the TPU lowering has no direct uint8 -> float32 cast
    x = img_ref[0].astype(jnp.int32).astype(jnp.float32) / 255.0
    xf = flip_ref[0].astype(jnp.int32).astype(jnp.float32) / 255.0
    row = jax.lax.broadcasted_iota(jnp.int32, (h, wc), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (h, wc), 1) // channels

    def inside(ref, base):
        top, left, eh, ew = (ref[base + n] for n in range(4))
        return (row >= top) & (row < top + eh) & (col >= left) & (col < left + ew)

    if erase_k:
        fill = fill_ref[...]
        for k in range(erase_k):
            x = jnp.where(inside(eb_ref, (i * erase_k + k) * 4), fill, x)
            # the mixup partner is the ERASED flipped row -> its own boxes
            xf = jnp.where(inside(eb_ref, (j * erase_k + k) * 4), fill, xf)
    lam = lam_ref[i]
    mixed = x * lam + xf * (1.0 - lam)
    yl, yh, xl, xh = (bbox_ref[i * 4 + n] for n in range(4))
    ins = (row >= yl) & (row < yh) & (col >= xl) & (col < xh)
    cut = jnp.where(ins, xf, x)
    x = jnp.where(cut_ref[i] != 0, cut, mixed)
    x = (x - mean_ref[...]) / std_ref[...]
    o_ref[0] = x.astype(o_ref.dtype)


def augment_epilogue(image, lam, use_cutmix, bbox, erase_box, *,
                     mean, std, re_mean, out_dtype=jnp.float32):
    """One-pass epilogue over (B, H, W, C) uint8 `image`. Per-image params:
    `lam` (B,) f32, `use_cutmix` (B,) bool/int, `bbox` (B, 4) and
    `erase_box` (B, K, 4) int (zero boxes are no-ops)."""
    b, h, w, c = image.shape
    k = int(erase_box.shape[1]) if erase_box.size else 0
    img2 = image.reshape(b, h, w * c)
    lam1 = jnp.asarray(lam, jnp.float32).reshape(b)
    cut1 = jnp.asarray(use_cutmix, jnp.int32).reshape(b)
    bbox1 = jnp.asarray(bbox, jnp.int32).reshape(b * 4)
    if k:
        eb1 = jnp.asarray(erase_box, jnp.int32).reshape(b * k * 4)
    else:
        eb1 = jnp.zeros((b * 4,), jnp.int32)

    mean_row = jnp.asarray(np.tile(np.asarray(mean, np.float32), w))[None]
    std_row = jnp.asarray(np.tile(np.asarray(std, np.float32), w))[None]
    fill_row = jnp.asarray(np.tile(np.asarray(re_mean, np.float32), w))[None]

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    row_spec = pl.BlockSpec((1, w * c), lambda i: (0, 0))
    kern = functools.partial(_epilogue_kernel, channels=c, erase_k=k)
    out = pl.pallas_call(
        kern,
        grid=(b,),
        in_specs=[
            smem,                                                 # lam
            smem,                                                 # use_cutmix
            smem,                                                 # cutmix bbox
            smem,                                                 # erase boxes
            row_spec,                                             # mean (W-tiled)
            row_spec,                                             # std
            row_spec,                                             # erase fill
            pl.BlockSpec((1, h, w * c), lambda i: (i, 0, 0)),     # image row
            pl.BlockSpec((1, h, w * c), lambda i: (b - 1 - i, 0, 0)),  # mix partner
        ],
        out_specs=pl.BlockSpec((1, h, w * c), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, w * c), out_dtype),
        interpret=_interpret(),
    )(lam1, cut1, bbox1, eb1, mean_row, std_row, fill_row, img2, img2)
    return out.reshape(b, h, w, c)


def augment_image_batch_fused(batch, *, mean, std, re_mode='const',
                              re_mean=(0.0, 0.0, 0.0), re_std=(1.0, 1.0, 1.0),
                              noise_seed=42, num_classes=0, smoothing=0.0,
                              out_dtype=jnp.float32):
    """Drop-in twin of `augment_image_batch` that routes the image epilogue
    through the fused kernel when the batch is in regime; target math (tiny)
    and out-of-regime erase modes stay on the XLA program."""
    from ..data.device_augment import augment_image_batch, mixup_targets

    if not augment_epilogue_supported(batch, re_mode):
        return augment_image_batch(
            batch, mean=mean, std=std, re_mode=re_mode, re_mean=re_mean,
            re_std=re_std, noise_seed=noise_seed, num_classes=num_classes,
            smoothing=smoothing, out_dtype=out_dtype)
    img = batch['image']
    b = img.shape[0]
    has_mix = 'lam' in batch
    x = augment_epilogue(
        img,
        batch.get('lam', jnp.ones((b,), jnp.float32)),
        batch.get('use_cutmix', jnp.zeros((b,), jnp.int32)),
        batch.get('bbox', jnp.zeros((b, 4), jnp.int32)),
        batch.get('erase_box', jnp.zeros((b, 0, 4), jnp.int32)),
        mean=mean, std=std, re_mean=re_mean, out_dtype=out_dtype)
    if has_mix:
        y = mixup_targets(batch['target'], batch['lam'], num_classes, smoothing)
    else:
        y = batch['target']
    return x, y


# ---------------------------------------------------------------------------
# registry entry


def _make_inputs(seed: int = 0, batch: int = 8, size: int = 32,
                 erase_k: int = 1, with_mix: bool = True,
                 with_erase: bool = True, num_classes: int = 10):
    rng = np.random.default_rng(seed)
    b, h = batch, size
    out = {
        'image': jnp.asarray(rng.integers(0, 256, (b, h, h, 3)), jnp.uint8),
        'target': jnp.asarray(rng.integers(0, num_classes, (b,)), jnp.int32),
    }
    if with_erase:
        boxes = np.zeros((b, erase_k, 4), np.int32)
        for i in range(b):
            for kk in range(erase_k):
                eh, ew = rng.integers(4, h // 2, 2)
                boxes[i, kk] = (rng.integers(0, h - eh), rng.integers(0, h - ew),
                                eh, ew)
        out['erase_box'] = jnp.asarray(boxes)
    if with_mix:
        yl = rng.integers(0, h // 2, (b,))
        xl = rng.integers(0, h // 2, (b,))
        out['lam'] = jnp.asarray(rng.uniform(0.2, 1.0, (b,)), jnp.float32)
        out['use_cutmix'] = jnp.asarray(rng.integers(0, 2, (b,)), bool)
        out['bbox'] = jnp.asarray(
            np.stack([yl, yl + h // 4, xl, xl + h // 4], 1), jnp.int32)
    return {'batch': out}


_STATICS = dict(mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225),
                re_mean=(0.485, 0.456, 0.406), num_classes=10, smoothing=0.1)


def _reference(batch, **statics):
    from ..data.device_augment import augment_image_batch
    return augment_image_batch(batch, **statics)


register(KernelSpec(
    name='augment_epilogue',
    module=__name__,
    regime="DeviceAugment 'const'-erase epilogue at loader batch shapes "
           '(e.g. 128x224x224x3 uint8): pure streaming that XLA runs as '
           'several full-canvas HBM passes, fused here to one read of the '
           'image + its mixup partner and one normalized write',
    gate='win wall-clock vs the jitted XLA augment program at the live '
         'loader shape on TPU — or delete (the XLA program stays for '
         "'pixel'/'rand' modes either way)",
    # float32 outputs reach |2.7| (ulp 2.4e-7). Interpreted on the CPU the two
    # arms agree exactly; compiled for the TPU, whose f32 divide is not
    # correctly rounded, Mosaic and XLA differ by a few ulp through /255 and
    # /std: 1.2e-6 measured on a v5e (chip_smoke.py, PR 21)
    parity_tol=5e-6,
    kernel_fn=augment_image_batch_fused,
    reference_fn=_reference,
    make_inputs=_make_inputs,
    cases=(
        KernelCase(
            name='mix_erase',
            dry=dict(batch=8, size=32, erase_k=1),
            live=dict(batch=128, size=224, erase_k=1),
            statics=dict(_STATICS),
            desc='mixup/cutmix + const erase + normalize, the full epilogue',
        ),
        KernelCase(
            name='no_mix',
            dry=dict(batch=8, size=32, with_mix=False),
            live=dict(batch=128, size=224, with_mix=False),
            statics=dict(_STATICS),
            desc='identity-mix regime (eval-style erase+normalize only)',
        ),
    ),
    backends=('tpu',),
))
