"""On-device data path (data/device_augment.py): host/device parity, seeded
param-sampling equivalence, pipeline stages, NaFlex packed batching, and the
zero-recompile-after-warmup contract.

The load-bearing invariant: the host pipeline (Mixup.__call__ / RandomErasing
.__call__ / normalize) and the device pipeline (sample_params on host + the
jitted appliers on device) compute the SAME math from the SAME RNG stream, so
flipping --device-augment changes where the float work runs, never what the
model sees.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from timm_tpu.data.device_augment import (
    DeviceAugmentStage, NaFlexDeviceAugment, augment_image_batch,
    augment_image_batch_np, augment_naflex_batch, batch_donate_argnums,
    erase_images, erase_images_np, mixup_images, mixup_images_np,
    mixup_targets, mixup_targets_np,
)
from timm_tpu.data.mixup import FastCollateMixup, Mixup
from timm_tpu.data.random_erasing import RandomErasing
from timm_tpu.utils.compile_cache import cache_event_total, collect_cache_events

pytestmark = pytest.mark.deviceaug

B, H, W, C, NC = 8, 16, 16, 3, 10


def _img01(seed=0, b=B, h=H, w=W):
    return np.random.RandomState(seed).rand(b, h, w, C).astype(np.float32)


# ---- 1. host __call__ vs sampled-params device appliers ---------------------

@pytest.mark.parametrize('mode', ['batch', 'elem', 'pair'])
@pytest.mark.parametrize('alphas', [(0.8, 0.0), (0.0, 1.0), (0.5, 0.5)])
def test_mixup_host_vs_device_parity(mode, alphas):
    """Identically seeded Mixup: pixels+targets from the host path equal the
    device appliers fed by sample_params to <=1e-6 (same RNG draw order)."""
    ma, ca = alphas
    kw = dict(mixup_alpha=ma, cutmix_alpha=ca, mode=mode, label_smoothing=0.1,
              num_classes=NC, seed=33)
    x = _img01(1)
    t = np.arange(B) % NC

    host_x, host_y = Mixup(**kw)(x.copy(), t)

    params = Mixup(**kw).sample_params(x.shape)
    dev_x = np.asarray(mixup_images(jnp.asarray(x), jnp.asarray(params['lam']),
                                    jnp.asarray(params['use_cutmix']),
                                    jnp.asarray(params['bbox'])))
    dev_y = np.asarray(mixup_targets(jnp.asarray(t), jnp.asarray(params['lam']),
                                     NC, 0.1))
    np.testing.assert_allclose(dev_x, host_x, atol=1e-6)
    np.testing.assert_allclose(dev_y, host_y, atol=1e-6)


@pytest.mark.parametrize('mode', ['const', 'rand'])
def test_random_erasing_host_vs_device_parity(mode):
    """Seeded RandomErasing: in-place host erase equals the broadcast-mask
    device applier fed by sample_params (identical rectangles and fills)."""
    kw = dict(probability=1.0, mode=mode, min_count=1, max_count=3,
              mean=(0.2, 0.3, 0.4), std=(0.5, 0.5, 0.5), seed=11)
    x = _img01(2)

    host = RandomErasing(**kw)(x.copy())

    params = RandomErasing(**kw).sample_params(x.shape)
    dev = np.asarray(erase_images(
        jnp.asarray(x), jnp.asarray(params['erase_box']),
        jnp.asarray(params['erase_fill']) if mode == 'rand' else None,
        mode=mode, mean=(0.2, 0.3, 0.4)))
    np.testing.assert_allclose(dev, host, atol=1e-6)
    assert (params['erase_box'][:, :, 2:] > 0).any(), 'p=1.0 must erase'


def test_sample_params_consumes_identical_rng_stream():
    """After host __call__ vs sample_params, the two seeded instances' RNG
    streams are in the SAME state — the next draws coincide, so --resume
    replay is bit-identical whichever path a run uses."""
    x, t = _img01(3), np.arange(B) % NC
    kw = dict(mixup_alpha=0.6, cutmix_alpha=0.4, mode='elem', num_classes=NC,
              seed=5)
    a, b = Mixup(**kw), Mixup(**kw)
    a(x.copy(), t)
    b.sample_params(x.shape)
    assert a._rng.random() == b._rng.random()

    rkw = dict(probability=0.7, mode='rand', max_count=2, seed=6)
    ra, rb = RandomErasing(**rkw), RandomErasing(**rkw)
    ra(x.copy())
    rb.sample_params(x.shape)
    assert ra._rng.random() == rb._rng.random()


def test_mixup_disabled_emits_identity_values():
    """mixup_off_epoch path: a disabled sampler keeps emitting the SAME pytree
    (lam=1, zero boxes) so the compiled program set never changes."""
    m = Mixup(mixup_alpha=0.8, cutmix_alpha=0.8, num_classes=NC, seed=1)
    m.mixup_enabled = False
    p = m.sample_params((B, H, W, C))
    assert (p['lam'] == 1.0).all() and not p['use_cutmix'].any()
    assert (p['bbox'] == 0).all()


# ---- 2. the fused device program vs its numpy oracle ------------------------

def _sampled_batch(re_mode):
    """Parameters as the loader's samplers draw them: 8 x 16 x 16, up to two erase boxes a row, batch-mode mix."""
    rng = np.random.RandomState(4)
    mix = Mixup(mixup_alpha=0.8, cutmix_alpha=1.0, mode='batch',
                num_classes=NC, seed=21)
    re = RandomErasing(probability=1.0, mode=re_mode, max_count=2,
                       mean=(0.1, 0.1, 0.1), std=(0.4, 0.4, 0.4), seed=22)
    batch = {'image': rng.randint(0, 256, (B, H, W, C)).astype(np.uint8),
             'target': (np.arange(B) % NC).astype(np.int64)}
    batch.update(re.sample_params(batch['image'].shape))
    batch.update(mix.sample_params(batch['image'].shape))
    if re_mode == 'pixel':
        batch['noise_epoch'] = np.uint32(3)
        batch['noise_step'] = np.uint32(7)
    return batch


def _hand_batch(seed=3, batch=8, size=32, erase_k=1, with_mix=True):
    """Hand-built parameters at another batch and size: `erase_k` boxes in EVERY row, a mix per row or none at all
    (no `lam`: the program returns the integer targets as they came)."""
    rng = np.random.default_rng(seed)
    b, h = batch, size
    out = {'image': rng.integers(0, 256, (b, h, h, 3)).astype(np.uint8),
           'target': rng.integers(0, NC, (b,)).astype(np.int32)}
    boxes = np.zeros((b, erase_k, 4), np.int32)
    for i in range(b):
        for kk in range(erase_k):
            eh, ew = rng.integers(4, h // 2, 2)
            boxes[i, kk] = (rng.integers(0, h - eh), rng.integers(0, h - ew), eh, ew)
    out['erase_box'] = boxes
    if with_mix:
        yl = rng.integers(0, h // 2, (b,))
        xl = rng.integers(0, h // 2, (b,))
        out['lam'] = rng.uniform(0.2, 1.0, (b,)).astype(np.float32)
        out['use_cutmix'] = rng.integers(0, 2, (b,)).astype(bool)
        out['bbox'] = np.stack([yl, yl + h // 4, xl, xl + h // 4], 1).astype(np.int32)
    return out


_ORACLE_CASES = {
    'const': ('const', lambda: _sampled_batch('const')),
    'rand': ('rand', lambda: _sampled_batch('rand')),
    'pixel': ('pixel', lambda: _sampled_batch('pixel')),
    'no_mix': ('const', lambda: _hand_batch(with_mix=False)),
    'two_boxes_b6_s24': ('const', lambda: _hand_batch(erase_k=2, batch=6, size=24)),
}


@pytest.mark.parametrize('case', list(_ORACLE_CASES))
def test_augment_image_batch_matches_np_oracle(case):
    """Full fused program (uint8 -> erase -> mixup -> normalize -> soft
    targets) against the eager numpy twin; 'pixel' exercises the on-device
    threaded-key noise, which the oracle reproduces via the same key;
    'no_mix' is the eval-style path (erase + normalize, hard targets out)."""
    re_mode, make_batch = _ORACLE_CASES[case]
    batch = make_batch()
    kw = dict(mean=(0.48, 0.45, 0.41), std=(0.22, 0.22, 0.22), re_mode=re_mode,
              re_mean=(0.1, 0.1, 0.1), re_std=(0.4, 0.4, 0.4), noise_seed=9,
              num_classes=NC, smoothing=0.1)
    x_np, y_np = augment_image_batch_np(batch, **kw)
    x_dev, y_dev = jax.jit(
        lambda bt: augment_image_batch(bt, **kw))(
            {k: jnp.asarray(v) for k, v in batch.items()})
    assert (y_dev.ndim == 1 and y_dev.dtype == jnp.int32) == (case == 'no_mix')
    np.testing.assert_allclose(np.asarray(x_dev), x_np, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y_dev), y_np, atol=1e-6)


def test_mixup_erase_appliers_match_oracles_elementwise():
    """The individual appliers and their numpy twins agree on hand-built
    params (cutmix bbox rows mixed with plain-lam rows in one batch)."""
    x = _img01(5)
    lam = np.linspace(0.1, 1.0, B).astype(np.float32)
    use_cutmix = (np.arange(B) % 2).astype(bool)
    bbox = np.zeros((B, 4), np.int32)
    bbox[use_cutmix] = (2, 10, 3, 12)
    np.testing.assert_allclose(
        np.asarray(mixup_images(jnp.asarray(x), jnp.asarray(lam),
                                jnp.asarray(use_cutmix), jnp.asarray(bbox))),
        mixup_images_np(x, lam, use_cutmix, bbox), atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(mixup_targets(jnp.asarray(np.arange(B) % NC),
                                 jnp.asarray(lam), NC, 0.1)),
        mixup_targets_np(np.arange(B) % NC, lam, NC, 0.1), atol=1e-6)

    boxes = np.zeros((B, 2, 4), np.int32)
    boxes[:, 0] = (1, 1, 4, 5)
    boxes[3:, 1] = (8, 2, 6, 6)  # second slot only for some rows
    np.testing.assert_allclose(
        np.asarray(erase_images(jnp.asarray(x), jnp.asarray(boxes),
                                mode='const', mean=(0.3, 0.3, 0.3))),
        erase_images_np(x, boxes, mode='const', mean=(0.3, 0.3, 0.3)),
        atol=1e-6)


# ---- 3. pipeline stages: determinism + zero recompiles ----------------------

class _FakeImageLoader:
    """Host loader stand-in: deterministic uint8 (image, target) batches over
    a small set of bucket shapes, same sequence every epoch."""

    def __init__(self, shapes, batches_per_shape=2):
        self.shapes = shapes
        self.n = batches_per_shape
        self.epoch = 0

    def set_epoch(self, epoch):
        self.epoch = epoch

    def __len__(self):
        return len(self.shapes) * self.n

    def __iter__(self):
        for i in range(self.n):
            for h, w in self.shapes:
                rng = np.random.RandomState(hash((h, w, i)) % (2 ** 31))
                yield (rng.randint(0, 256, (B, h, w, C)).astype(np.uint8),
                       (np.arange(B) % NC).astype(np.int64))


def _make_stage(mesh):
    mix = Mixup(mixup_alpha=0.8, cutmix_alpha=0.8, num_classes=NC, seed=17)
    re = RandomErasing(probability=1.0, mode='pixel', max_count=2, seed=18)
    return DeviceAugmentStage(
        _FakeImageLoader([(16, 16), (16, 24), (24, 24)]),
        mean=(0.5,) * 3, std=(0.25,) * 3, mixup=mix, random_erasing=re,
        re_mode='pixel', noise_seed=19, mesh=mesh)


def test_device_augment_stage_epoch_replay_is_deterministic(mesh8):
    """set_epoch(e) fully re-derives every stream (mixup, erase, pixel noise):
    two independent stages replay identical device batches — the --resume
    auto contract for the on-device path."""

    def run_epoch(stage, epoch):
        stage.set_epoch(epoch)
        return [(np.asarray(x), np.asarray(y)) for x, y in stage]

    a = run_epoch(_make_stage(mesh8), 4)
    b = run_epoch(_make_stage(mesh8), 4)
    c = run_epoch(_make_stage(mesh8), 5)
    assert len(a) == len(b) == 6
    for (xa, ya), (xb, yb) in zip(a, b):
        np.testing.assert_array_equal(xa, xb)
        np.testing.assert_array_equal(ya, yb)
    assert any(not np.array_equal(xa, xc) for (xa, _), (xc, _) in zip(a, c)), \
        'different epochs must draw different augmentations'


def test_device_augment_stage_zero_recompiles_after_warmup(mesh8):
    """The bucketed-shape contract: after one epoch over all (H, W) buckets,
    a second epoch triggers ZERO fresh XLA compiles (identity is encoded in
    param values, pytree structure is shape-stable)."""
    stage = _make_stage(mesh8)
    stage.set_epoch(0)
    for x, _ in stage:
        jax.block_until_ready(x)
    stage.set_epoch(1)
    with collect_cache_events() as counts:
        for x, _ in stage:
            jax.block_until_ready(x)
    assert cache_event_total(counts, 'cache_misses') == 0, counts


class _FakePackedLoader:
    """NaFlex loader stand-in: deterministic packed dict batches over a
    seq-len bucket ladder, [0,1] patches + erase_mask (device-augment host
    contract)."""

    def __init__(self, seq_lens=(16, 25, 36), patch_size=4):
        self.seq_lens = seq_lens
        self.p = patch_size

    def __len__(self):
        return len(self.seq_lens)

    def __iter__(self):
        for sl in self.seq_lens:
            rng = np.random.RandomState(sl)
            gw = int(np.sqrt(sl))
            coord = np.stack(np.meshgrid(np.arange(sl // gw), np.arange(gw),
                                         indexing='ij'), -1).reshape(-1, 2)
            n = len(coord)
            yield {
                'patches': rng.rand(B, sl, self.p * self.p * C).astype(np.float32),
                'patch_coord': np.tile(np.pad(coord, ((0, sl - n), (0, 0))), (B, 1, 1)).astype(np.int32),
                'patch_valid': np.tile(np.arange(sl) < n, (B, 1)),
                'target': (np.arange(B) % NC).astype(np.int64),
                'erase_mask': np.tile(np.arange(sl) % 5 == 0, (B, 1)),
                'seq_len': sl,
            }


def test_naflex_device_augment_stage_parity_and_zero_recompiles(mesh8):
    """The per-bucket naflex program normalizes and fills erased
    token slots exactly like the host path (normalize-then-const-0 fill),
    strips the param keys, keeps host metadata — and a second epoch over the
    same ladder compiles nothing."""
    mean = std = (0.5, 0.5, 0.5)
    stage = NaFlexDeviceAugment(_FakePackedLoader(), mean=mean, std=std,
                                re_mode='const', mesh=mesh8)
    host_batches = list(_FakePackedLoader())
    for out, src in zip(stage, host_batches):
        assert 'erase_mask' not in out and out['seq_len'] == src['seq_len']
        expect = (src['patches'] - 0.5) / 0.5
        expect = np.where(src['erase_mask'][..., None], 0.0, expect)
        np.testing.assert_allclose(np.asarray(out['patches']), expect, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(out['patch_valid']),
                                      src['patch_valid'])
    with collect_cache_events() as counts:
        for out in stage:
            jax.block_until_ready(out['patches'])
    assert cache_event_total(counts, 'cache_misses') == 0, counts


# ---- 4. NaFlex packed-vs-unpacked forward -----------------------------------

def test_naflex_packed_padding_invariance_forward():
    """The model output over valid tokens must not depend on (a) how much a
    batch is padded to reach its bucket or (b) the garbage occupying padded
    slots: packing variable-resolution images into a shared bucket is
    semantically free."""
    import timm_tpu

    model = timm_tpu.create_model('test_naflexvit', num_classes=NC)
    model.eval()
    p = model.embeds.patch_size
    rng = np.random.RandomState(8)
    gh = gw = 4
    n = gh * gw
    coord = np.stack(np.meshgrid(np.arange(gh), np.arange(gw),
                                 indexing='ij'), -1).reshape(-1, 2)

    def forward(L, junk):
        patches = np.zeros((B, L, p * p * C), np.float32)
        patches[:, :n] = np.random.RandomState(8).rand(B, n, p * p * C)
        if junk:
            patches[:, n:] = rng.rand(B, L - n, p * p * C) * 100
        pc = np.zeros((B, L, 2), np.int32)
        pc[:, :n] = coord
        return np.asarray(model({
            'patches': jnp.asarray(patches),
            'patch_coord': jnp.asarray(pc),
            'patch_valid': jnp.asarray(np.arange(L)[None] < n).repeat(B, 0),
        }))

    exact = forward(n, junk=False)
    padded = forward(n + 9, junk=False)
    padded_junk = forward(n + 9, junk=True)
    np.testing.assert_allclose(padded, exact, atol=1e-5)
    np.testing.assert_allclose(padded_junk, exact, atol=1e-5)


def test_naflex_attention_mask_tolerates_integer_valid():
    """Post-transfer masks may arrive as uint8/int32 — the attention mask
    builder casts, so a loader handing over non-bool validity cannot flip
    attention weights."""
    from timm_tpu.models.naflexvit import create_attention_mask
    valid = np.array([[1, 1, 0, 0], [1, 1, 1, 0]], np.uint8)
    m_int = create_attention_mask(jnp.asarray(valid))
    m_bool = create_attention_mask(jnp.asarray(valid.astype(bool)))
    np.testing.assert_array_equal(np.asarray(m_int), np.asarray(m_bool))


# ---- 5. loader wiring: config errors + budgets ------------------------------

def test_create_loader_rejects_fast_collate_mixup_and_eval():
    from timm_tpu.data import create_loader

    class _DS:
        def __getitem__(self, i):
            raise IndexError

        def __len__(self):
            return 0

    fcm = FastCollateMixup(num_classes=NC)
    with pytest.raises(ValueError, match='double-apply'):
        create_loader(_DS(), (3, 16, 16), 8, is_training=True,
                      device_augment=True, mixup=fcm)
    with pytest.raises(ValueError, match='train-path'):
        create_loader(_DS(), (3, 16, 16), 8, is_training=False,
                      device_augment=True)


def test_naflex_loader_native_mode_validation():
    from timm_tpu.data.naflex_loader import NaFlexLoader

    class _DS:
        transform = None

        def __len__(self):
            return 0

        def __getitem__(self, i):
            raise IndexError

    with pytest.raises(ValueError, match='bucket_mode'):
        NaFlexLoader(_DS(), bucket_mode='nope')
    with pytest.raises(ValueError, match='multi-host|process'):
        NaFlexLoader(_DS(), bucket_mode='native', process_count=2)
    with pytest.raises(ValueError, match='patch_size'):
        NaFlexLoader(_DS(), bucket_mode='native',
                     patch_size_choices=(8, 16))


@pytest.mark.perfbudget
def test_device_augment_probes_within_budgets():
    """The two on-device data-path probe configs stay within their checked-in
    budgets (trace_ms excluded in-process, same policy as the seed-budget
    test: warmth-sensitive; every deterministic metric has full teeth)."""
    from timm_tpu.perfbudget import compare_budgets, format_violations, load_budgets
    from timm_tpu.perfbudget.probe import run_matrix

    names = ['device_augment', 'naflex_packed']
    measured = run_matrix(names=names)
    violations = [v for v in compare_budgets(measured, load_budgets(), configs=names)
                  if v['metric'] != 'trace_ms']
    assert not violations, format_violations(violations)
    assert measured['device_augment']['naflex_donation_ok']
    assert measured['naflex_packed']['donation_ok']


def test_batch_donation_gated_off_on_cpu(monkeypatch):
    """A donated augment program deserialized from the persistent compile
    cache returns corrupted buffers on XLA:CPU (fresh compiles are fine; the
    poison bites the second warm-cache process), so the runtime stages must
    not request donation on the CPU backend — and must keep it on
    accelerators, where freeing the staged batch buffers is the point."""
    assert jax.default_backend() == 'cpu'
    assert batch_donate_argnums() == ()
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    assert batch_donate_argnums() == (0,)
