"""On-device batch augmentation (ROADMAP item 3).

Mixup/CutMix blending + soft-target construction, RandomErasing region fill,
and normalize/dtype-cast re-expressed as pure jittable functions that run on
the accelerator *after* transfer, so the host stages only decode, resize and
collate uint8. Each transform is split in two:

  * host-side **parameter sampling** — ``Mixup.sample_params`` /
    ``RandomErasing.sample_params`` draw lam, cutmix bboxes and erase
    rectangles as tiny arrays that ride the batch;
  * device-side **application** — the functions below consume those params
    with pure jnp math (broadcast coordinate masks, never dynamic slicing),
    so the jitted program is shape-stable: one compile per batch shape, zero
    recompiles after warmup.

Identity is always encoded in *values* (lam=1, zero boxes), never in pytree
structure, so every batch of a given shape hits the same compiled program.
'pixel'-mode erase noise is the one draw that happens on device, from a
``jax.random`` key threaded as (seed, epoch, step) — deterministic and
resumable without shipping a (B, H, W, C) noise canvas over PCIe.

Numpy twins of every applier live here too; they are the parity oracle for
tests and the documentation of exactly what the device program computes.
"""
from __future__ import annotations

import functools
import warnings

import numpy as np

import jax
import jax.numpy as jnp

from timm_tpu.parallel.mesh import shard_batch
from timm_tpu.utils import tracing

__all__ = [
    'mixup_images', 'mixup_targets', 'erase_images', 'augment_image_batch',
    'augment_naflex_batch', 'mixup_images_np', 'mixup_targets_np',
    'erase_images_np', 'augment_image_batch_np', 'pixel_noise',
    'DeviceAugment', 'DeviceAugmentStage', 'NaFlexDeviceAugment',
    'batch_donate_argnums',
]

# donating the uint8 image buffer frees it as soon as the program runs, but it
# can never alias the float32 output; silence the (per-compile) jax warning
warnings.filterwarnings('ignore', message='Some donated buffers were not usable')


def batch_donate_argnums():
    """Donation spec for the augment programs: `(0,)` (donate the batch dict)
    on accelerator backends, `()` on CPU.

    XLA:CPU mis-executes these programs after a persistent-compile-cache
    round-trip when their inputs are donated: the freshly compiled executable
    is correct (and gets persisted), but the DESERIALIZED executable returns
    corrupted buffers — garbage/NaN patches — on every later warm process.
    The donated train step round-trips fine, so the defect is specific to
    this program shape (identity pass-through outputs aliasing donated
    inputs). Donation only pays for itself in accelerator HBM anyway, so it
    is gated on the backend rather than dropped outright."""
    return () if jax.default_backend() == 'cpu' else (0,)


def _noise_key(noise_seed, epoch, step):
    key = jax.random.fold_in(jax.random.PRNGKey(noise_seed), epoch)
    return jax.random.fold_in(key, step)


def pixel_noise(shape, noise_seed, epoch, step, mean=None, std=None):
    """The 'pixel'-mode erase fill canvas: mean + std * N(0, 1), generated
    from a (seed, epoch, step)-threaded key. Runs under jit on device; the
    numpy parity oracle calls it eagerly and converts — jax.random is
    deterministic across both."""
    noise = jax.random.normal(_noise_key(noise_seed, epoch, step), shape, jnp.float32)
    if mean is not None:
        noise = jnp.asarray(mean, jnp.float32) + jnp.asarray(std, jnp.float32) * noise
    return noise


# -- device appliers ----------------------------------------------------------

def mixup_images(x, lam, use_cutmix, bbox):
    """Blend (B, H, W, C) float x with its batch flip. Per-row params unify
    the host batch/elem/pair modes: row i mixes with original row B-1-i using
    lam[i]; cutmix rows paste the bbox[i]=(yl, yh, xl, xh) region instead."""
    x_flip = x[::-1]
    lam_b = lam[:, None, None, None]
    mixed = x * lam_b + x_flip * (1.0 - lam_b)
    yy = jnp.arange(x.shape[1])[None, :, None]
    xx = jnp.arange(x.shape[2])[None, None, :]
    yl, yh, xl, xh = (bbox[:, i][:, None, None] for i in range(4))
    inside = (yy >= yl) & (yy < yh) & (xx >= xl) & (xx < xh)
    cut = jnp.where(inside[..., None], x_flip, x)
    return jnp.where(use_cutmix[:, None, None, None], cut, mixed)


def mixup_targets(target, lam, num_classes, smoothing=0.0):
    """Per-row soft targets: smoothed one-hot of target blended with the
    batch-flipped labels (mixup.mixup_target generalized to vector lam)."""
    off = smoothing / num_classes
    on = 1.0 - smoothing + off
    y1 = jax.nn.one_hot(target, num_classes, dtype=jnp.float32) * (on - off) + off
    y2 = jax.nn.one_hot(target[::-1], num_classes, dtype=jnp.float32) * (on - off) + off
    return y1 * lam[:, None] + y2 * (1.0 - lam[:, None])


def erase_images(x, erase_box, fill=None, *, mode='const', mean=(0.0, 0.0, 0.0),
                 noise=None):
    """Fill K rectangles per row. erase_box is (B, K, 4) = (top, left, eh, ew);
    zero boxes are no-ops. Fill source by (static) mode: 'const' uses the
    channel color `mean`, 'rand' indexes `fill` (B, K, C), 'pixel' reads the
    `noise` canvas (B, H, W, C). Boxes apply in slot order (last write wins,
    like the host's sequential in-place stores)."""
    yy = jnp.arange(x.shape[1])[None, :, None]
    xx = jnp.arange(x.shape[2])[None, None, :]
    mean_c = jnp.asarray(mean, x.dtype)
    for k in range(erase_box.shape[1]):
        top, left, eh, ew = (erase_box[:, k, j][:, None, None] for j in range(4))
        inside = (yy >= top) & (yy < top + eh) & (xx >= left) & (xx < left + ew)
        if mode == 'pixel':
            fill_k = noise
        elif mode == 'rand':
            fill_k = fill[:, k][:, None, None, :]
        else:
            fill_k = mean_c
        x = jnp.where(inside[..., None], fill_k, x)
    return x


def augment_image_batch(batch, *, mean, std, re_mode='const',
                        re_mean=(0.0, 0.0, 0.0), re_std=(1.0, 1.0, 1.0),
                        noise_seed=42, num_classes=0, smoothing=0.0,
                        out_dtype=jnp.float32):
    """The fused device program: uint8 -> [0,1] float -> erase -> mixup ->
    normalize -> cast, mirroring the host pipeline order (loader collate
    erase, train-loop mixup, task normalize). `batch` carries the image, the
    int target, and the sampled params; returns (input, target) where target
    is the soft matrix when mixup params ride the batch."""
    x = batch['image'].astype(jnp.float32) / 255.0
    if 'erase_box' in batch:
        noise = None
        if re_mode == 'pixel':
            noise = pixel_noise(x.shape, noise_seed, batch['noise_epoch'],
                                batch['noise_step'], re_mean, re_std)
        x = erase_images(x, batch['erase_box'], batch.get('erase_fill'),
                         mode=re_mode, mean=re_mean, noise=noise)
    if 'lam' in batch:
        x = mixup_images(x, batch['lam'], batch['use_cutmix'], batch['bbox'])
        y = mixup_targets(batch['target'], batch['lam'], num_classes, smoothing)
    else:
        y = batch['target']
    x = (x - jnp.asarray(mean, jnp.float32)) / jnp.asarray(std, jnp.float32)
    return x.astype(out_dtype), y


def augment_naflex_batch(batch, *, mean, std, re_mode='const', noise_seed=42):
    """NaFlex packed variant: normalize (B, L, D) patches with per-channel
    mean/std tiled to the (P*P*C,) patch dim (channel-fastest flatten order),
    then fill erased token slots — in normalized space, matching the host
    NaFlexRandomErasing ('pixel' draws device noise from the threaded key,
    'const' fills 0). Param keys are consumed; everything else (coords, valid
    mask, targets) passes through for the train step."""
    p = batch['patches'].astype(jnp.float32)
    reps = p.shape[-1] // len(mean)
    p = (p - jnp.tile(jnp.asarray(mean, jnp.float32), reps)) / \
        jnp.tile(jnp.asarray(std, jnp.float32), reps)
    if 'erase_mask' in batch:
        if re_mode == 'pixel':
            fill = pixel_noise(p.shape, noise_seed, batch['noise_epoch'],
                               batch['noise_step'])
        else:
            fill = jnp.zeros((), jnp.float32)
        p = jnp.where(batch['erase_mask'][..., None], fill, p)
    out = {k: v for k, v in batch.items()
           if k not in ('erase_mask', 'noise_epoch', 'noise_step')}
    out['patches'] = p
    return out


# -- numpy parity oracles -----------------------------------------------------

def mixup_images_np(x, lam, use_cutmix, bbox):
    x = np.asarray(x, np.float32)
    x_flip = x[::-1]
    lam_b = np.asarray(lam, np.float32)[:, None, None, None]
    mixed = x * lam_b + x_flip * (1.0 - lam_b)
    yy = np.arange(x.shape[1])[None, :, None]
    xx = np.arange(x.shape[2])[None, None, :]
    yl, yh, xl, xh = (bbox[:, i][:, None, None] for i in range(4))
    inside = (yy >= yl) & (yy < yh) & (xx >= xl) & (xx < xh)
    cut = np.where(inside[..., None], x_flip, x)
    return np.where(np.asarray(use_cutmix)[:, None, None, None], cut, mixed)


def mixup_targets_np(target, lam, num_classes, smoothing=0.0):
    from timm_tpu.data.mixup import one_hot
    off = smoothing / num_classes
    on = 1.0 - smoothing + off
    y1 = one_hot(np.asarray(target), num_classes, on, off)
    y2 = one_hot(np.asarray(target)[::-1], num_classes, on, off)
    lam = np.asarray(lam, np.float32)[:, None]
    return y1 * lam + y2 * (1.0 - lam)


def erase_images_np(x, erase_box, fill=None, *, mode='const',
                    mean=(0.0, 0.0, 0.0), noise=None):
    x = np.array(x, np.float32)
    for i in range(x.shape[0]):
        for k in range(erase_box.shape[1]):
            top, left, eh, ew = (int(v) for v in erase_box[i, k])
            if eh == 0 or ew == 0:
                continue
            if mode == 'pixel':
                x[i, top:top + eh, left:left + ew] = noise[i, top:top + eh, left:left + ew]
            elif mode == 'rand':
                x[i, top:top + eh, left:left + ew] = fill[i, k]
            else:
                x[i, top:top + eh, left:left + ew] = np.asarray(mean, np.float32)
    return x


def augment_image_batch_np(batch, *, mean, std, re_mode='const',
                           re_mean=(0.0, 0.0, 0.0), re_std=(1.0, 1.0, 1.0),
                           noise_seed=42, num_classes=0, smoothing=0.0,
                           out_dtype=np.float32):
    x = np.asarray(batch['image']).astype(np.float32) / 255.0
    if 'erase_box' in batch:
        noise = None
        if re_mode == 'pixel':
            noise = np.asarray(pixel_noise(
                x.shape, noise_seed, int(batch['noise_epoch']),
                int(batch['noise_step']), re_mean, re_std))
        x = erase_images_np(x, batch['erase_box'], batch.get('erase_fill'),
                            mode=re_mode, mean=re_mean, noise=noise)
    if 'lam' in batch:
        x = mixup_images_np(x, batch['lam'], batch['use_cutmix'], batch['bbox'])
        y = mixup_targets_np(batch['target'], batch['lam'], num_classes, smoothing)
    else:
        y = np.asarray(batch['target'])
    x = (x - np.asarray(mean, np.float32)) / np.asarray(std, np.float32)
    return x.astype(out_dtype), y


# -- pipeline stages ----------------------------------------------------------

class DeviceAugment:
    """One jitted augment program; jit re-specializes per batch shape
    (bucketed loaders hit a small fixed program set, zero recompiles after
    warmup). On accelerators the batch is donated, freeing the staged
    uint8/param buffers as soon as the program runs (see
    batch_donate_argnums for why CPU is excluded)."""

    def __init__(self, mean, std, re_mode='const', re_mean=None, re_std=None,
                 num_classes=0, smoothing=0.0, noise_seed=42,
                 out_dtype=jnp.float32):
        self.fn = jax.jit(functools.partial(
            augment_image_batch,
            mean=tuple(mean), std=tuple(std), re_mode=re_mode,
            re_mean=tuple(re_mean if re_mean is not None else (0.0,) * len(mean)),
            re_std=tuple(re_std if re_std is not None else (1.0,) * len(std)),
            noise_seed=noise_seed, num_classes=num_classes, smoothing=smoothing,
            out_dtype=out_dtype), donate_argnums=batch_donate_argnums())

    def __call__(self, batch):
        return self.fn(batch)


class DeviceAugmentStage:
    """Iterable stage: consumes uint8 (image, target) batches from a loader
    (or a DevicePrefetcher wrapping one), samples augmentation params on the
    host, and yields (input, target) device arrays produced by the donated
    jitted augment program — soft targets when a Mixup sampler is attached."""

    def __init__(self, loader, mean, std, mixup=None, random_erasing=None,
                 re_mode='const', noise_seed=42, out_dtype=jnp.float32,
                 mesh=None):
        self.loader = loader
        self.mixup = mixup
        self.random_erasing = random_erasing
        self.re_mode = re_mode
        self._mesh = mesh
        self._epoch = 0
        self._augment = DeviceAugment(
            mean, std, re_mode=re_mode,
            re_mean=getattr(random_erasing, 'mean', None),
            re_std=getattr(random_erasing, 'std', None),
            num_classes=getattr(mixup, 'num_classes', 0),
            smoothing=getattr(mixup, 'label_smoothing', 0.0),
            noise_seed=noise_seed, out_dtype=out_dtype)

    def set_epoch(self, epoch: int):
        self._epoch = int(epoch)
        if hasattr(self.loader, 'set_epoch'):
            self.loader.set_epoch(epoch)
        if self.mixup is not None:
            self.mixup.set_epoch(epoch)
        if self.random_erasing is not None:
            self.random_erasing.set_epoch(epoch)

    def __len__(self):
        return len(self.loader)

    def __getattr__(self, name):
        return getattr(self.loader, name)

    def __iter__(self):
        for step, (x, t) in enumerate(self.loader):
            batch = {'image': x, 'target': t}
            with tracing.span('loader.sample_params'):
                if self.random_erasing is not None:
                    batch.update(self.random_erasing.sample_params(x.shape))
                    if self.re_mode == 'pixel':
                        batch['noise_epoch'] = np.uint32(self._epoch)
                        batch['noise_step'] = np.uint32(step)
                if self.mixup is not None:
                    batch.update(self.mixup.sample_params(x.shape))
            with tracing.span('loader.augment_call'):
                out = self._augment(shard_batch(batch, self._mesh))
            yield out


class NaFlexDeviceAugment:
    """Iterable stage for packed NaFlex dict batches: normalize + token erase
    run on device under one donated program per bucket shape; host metadata
    ('seq_len', 'patch_size') and param keys are kept out of / stripped from
    the device dict, so the yielded batch feeds the train step directly."""

    _HOST_KEYS = ('seq_len', 'patch_size')

    def __init__(self, loader, mean, std, re_mode='const', noise_seed=42,
                 mesh=None):
        self.loader = loader
        self.re_mode = re_mode
        self._mesh = mesh
        self._epoch = 0
        self.fn = jax.jit(functools.partial(
            augment_naflex_batch, mean=tuple(mean), std=tuple(std),
            re_mode=re_mode, noise_seed=noise_seed),
            donate_argnums=batch_donate_argnums())

    def set_epoch(self, epoch: int):
        self._epoch = int(epoch)
        if hasattr(self.loader, 'set_epoch'):
            self.loader.set_epoch(epoch)

    def __len__(self):
        return len(self.loader)

    def __getattr__(self, name):
        return getattr(self.loader, name)

    def __iter__(self):
        for step, batch in enumerate(self.loader):
            host_meta = {k: batch[k] for k in self._HOST_KEYS if k in batch}
            dev = {k: v for k, v in batch.items() if k not in host_meta}
            if self.re_mode == 'pixel' and 'erase_mask' in dev:
                dev['noise_epoch'] = np.uint32(self._epoch)
                dev['noise_step'] = np.uint32(step)
            out = self.fn(shard_batch(dev, self._mesh))
            out.update(host_meta)
            yield out
