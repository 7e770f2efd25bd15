"""Data pipeline tests (reference: tests dir lacks loader tests; transforms/
mixup invariants modeled on timm test style)."""
import os

import numpy as np
import pytest
from PIL import Image

from timm_tpu.data import (
    Mixup, RandomErasing, create_dataset, create_loader, create_transform,
    rand_augment_transform, resolve_data_config,
)


@pytest.fixture(scope='module')
def image_root(tmp_path_factory):
    root = tmp_path_factory.mktemp('imgs')
    rng = np.random.RandomState(0)
    for split in ('train', 'val'):
        for cls in ('a', 'b'):
            d = root / split / cls
            d.mkdir(parents=True)
            for i in range(6 if split == 'train' else 3):
                Image.fromarray(rng.randint(0, 255, (48, 56, 3), np.uint8)).save(d / f'{i}.jpg')
    return str(root)


def test_dataset_folder(image_root):
    ds = create_dataset('', root=image_root, split='train')
    assert len(ds) == 12
    assert ds.reader.class_to_idx == {'a': 0, 'b': 1}
    img, target = ds[0]
    assert target in (0, 1)


def test_dataset_split_search(image_root):
    ds = create_dataset('', root=image_root, split='validation')  # resolves to val/
    assert len(ds) == 6


def test_train_loader(image_root):
    ds = create_dataset('', root=image_root, split='train', is_training=True)
    loader = create_loader(ds, input_size=(3, 32, 32), batch_size=4, is_training=True,
                           num_workers=2, auto_augment='rand-m5', re_prob=0.3)
    batches = list(loader)
    assert len(batches) == 3  # 12 samples, drop_last
    x, t = batches[0]
    assert x.shape == (4, 32, 32, 3) and x.dtype == np.float32
    assert 0.0 <= x.min() and x.max() <= 1.0
    assert len(loader) == 3


def test_eval_loader_keeps_tail(image_root):
    ds = create_dataset('', root=image_root, split='val')
    loader = create_loader(ds, input_size=(3, 32, 32), batch_size=4, is_training=False)
    batches = list(loader)
    assert sum(b[0].shape[0] for b in batches) == 6  # no samples dropped


def test_loader_deterministic_order_eval(image_root):
    ds = create_dataset('', root=image_root, split='val')
    loader = create_loader(ds, input_size=(3, 32, 32), batch_size=3, is_training=False, num_workers=3)
    t1 = np.concatenate([b[1] for b in loader])
    t2 = np.concatenate([b[1] for b in loader])
    assert np.array_equal(t1, t2)


def test_transform_shapes():
    img = Image.fromarray(np.random.RandomState(0).randint(0, 255, (60, 80, 3), np.uint8))
    for is_training in (True, False):
        tf = create_transform(48, is_training=is_training)
        out = tf(img)
        assert out.shape == (48, 48, 3)


def test_rand_augment_config():
    ra = rand_augment_transform('rand-m9-mstd0.5-inc1', {})
    assert ra.num_layers == 2
    assert all(op.magnitude == 9 for op in ra.ops)
    assert all(op.magnitude_std == 0.5 for op in ra.ops)
    names = {op.name for op in ra.ops}
    assert 'PosterizeIncreasing' in names  # inc1 selected increasing set
    img = Image.fromarray(np.random.RandomState(0).randint(0, 255, (40, 40, 3), np.uint8))
    out = ra(img)
    assert out.size == (40, 40)


def test_mixup_batch_mode():
    rng = np.random.RandomState(0)
    x = rng.rand(8, 16, 16, 3).astype(np.float32)
    t = rng.randint(0, 10, 8)
    mix = Mixup(mixup_alpha=1.0, cutmix_alpha=1.0, num_classes=10, label_smoothing=0.1)
    xm, tm = mix(x, t)
    assert xm.shape == x.shape and tm.shape == (8, 10)
    np.testing.assert_allclose(tm.sum(-1), np.ones(8), rtol=1e-5)


def test_mixup_elem_mode():
    rng = np.random.RandomState(0)
    x = rng.rand(8, 16, 16, 3).astype(np.float32)
    t = rng.randint(0, 10, 8)
    mix = Mixup(mixup_alpha=1.0, mode='elem', num_classes=10)
    xm, tm = mix(x, t)
    assert xm.shape == x.shape and tm.shape == (8, 10)


def test_random_erasing():
    rng = np.random.RandomState(0)
    x = np.ones((4, 32, 32, 3), np.float32)
    re = RandomErasing(probability=1.0, mode='const')
    out = re(x.copy())
    assert (out == 0).any()  # something was erased
    re_none = RandomErasing(probability=0.0)
    out2 = re_none(x.copy())
    assert (out2 == 1).all()


def test_resolve_data_config_priority():
    cfg = resolve_data_config(
        {'img_size': 192, 'mean': (0.1,), 'crop_pct': 0.8},
        pretrained_cfg={'input_size': (3, 224, 224), 'mean': (0.5, 0.5, 0.5), 'std': (0.2, 0.2, 0.2)})
    assert cfg['input_size'] == (3, 192, 192)
    assert cfg['mean'] == (0.1, 0.1, 0.1)  # single value expanded
    assert cfg['std'] == (0.2, 0.2, 0.2)
    assert cfg['crop_pct'] == 0.8


def test_repeat_aug_sampler_semantics(tmp_path):
    """RepeatAugSampler: replicas see different repeats of the same shuffled
    order; per-replica count ~len/replicas (reference distributed_sampler.py:54)."""
    import numpy as np
    from timm_tpu.data.loader import ThreadedLoader

    class FakeDs:
        def __len__(self):
            return 300

        def __getitem__(self, i):
            return np.zeros((8, 8, 3), np.float32), i

    per_rank = []
    for rank in range(3):
        loader = ThreadedLoader(
            FakeDs(), batch_size=4, is_training=True, num_aug_repeats=3,
            process_index=rank, process_count=3, seed=0)
        idx = loader._shard_indices(shuffled=True)
        per_rank.append(list(idx))
    # reference defaults: floor(300/256*256/3) = 85 selected per rank
    assert all(len(ix) == 85 for ix in per_rank)
    # the three replicas start from the same repeated sequence offset by one:
    # each sample index appears on multiple replicas (different augs per replica)
    combined = per_rank[0] + per_rank[1] + per_rank[2]
    from collections import Counter
    counts = Counter(combined)
    assert max(counts.values()) == 3, 'a sample should repeat across replicas'
    # all replicas sample from the same shuffled epoch order
    loader2 = ThreadedLoader(
        FakeDs(), batch_size=4, is_training=True, num_aug_repeats=3,
        process_index=0, process_count=3, seed=0)
    assert list(loader2._shard_indices(shuffled=True)) == per_rank[0]


def test_augmix_jsd_splitbn_pipeline(tmp_path):
    """AugMix aug-splits end-to-end: tuple collate, JSD loss, split BN
    (reference train.py:886-913 + dataset.py:170)."""
    import numpy as np
    from PIL import Image

    from timm_tpu.data import create_dataset, create_loader
    from timm_tpu.data.dataset import AugMixDataset
    from timm_tpu.layers import convert_splitbn_model
    from timm_tpu.loss import JsdCrossEntropy
    import timm_tpu

    for cls in ('a', 'b'):
        d = tmp_path / 'train' / cls
        d.mkdir(parents=True)
        for i in range(4):
            Image.fromarray((np.random.rand(64, 64, 3) * 255).astype('uint8')).save(d / f'{i}.jpg')

    ds = create_dataset('', root=str(tmp_path), split='train', is_training=True)
    ds = AugMixDataset(ds, num_splits=3)
    loader = create_loader(
        ds, input_size=(3, 64, 64), batch_size=4, is_training=True,
        num_aug_splits=3, num_workers=0, auto_augment='augmix-m3-w2')
    x, t = next(iter(loader))
    assert x.shape == (12, 64, 64, 3)  # 4 samples x 3 splits, split-major
    assert t.shape == (12,)
    assert (t[:4] == t[4:8]).all() and (t[:4] == t[8:]).all()

    import jax.numpy as jnp

    model = timm_tpu.create_model('test_efficientnet', num_classes=5)
    model = convert_splitbn_model(model, 3)
    model.train()
    out = model(jnp.asarray(x, jnp.float32) / 255.0)
    loss = JsdCrossEntropy(num_splits=3, smoothing=0.1)(out, jnp.asarray(t))
    assert bool(jnp.isfinite(loss))


# The silent-exception-swallow lint is now the analysis rule `silent-except`
# (timm_tpu/analysis/source_rules.py) — widened from timm_tpu/data to the
# whole package plus the top-level scripts, enforced by tests/test_analysis.py.


class _Numbered:
    """A dataset whose sample IS its index: what the loader delivers can be counted."""

    def __init__(self, n):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return np.full((2, 2, 3), i % 251, np.uint8), int(i)


@pytest.mark.parametrize('n,batch,workers,training', [
    (37, 4, 3, False),     # nothing fills a chunk evenly: every worker's last hand-over is a partial one
    (37, 4, 3, True),
    (5, 2, 7, False),      # more workers than samples: most hand over nothing at all
    (64, 8, 2, True),      # whole chunks only
])
def test_samples_travel_in_chunks_and_every_one_arrives_once(n, batch, workers, training):
    """The decode threads hand the collator `_CHUNK` samples at a time and the
    rest when their indices end: no sample is lost or doubled, evaluation
    keeps index order, training drops only the incomplete last batch."""
    from timm_tpu.data import loader as loader_mod
    assert loader_mod._CHUNK > 1
    loader = loader_mod.ThreadedLoader(_Numbered(n), batch_size=batch, is_training=training, num_workers=workers, seed=3)
    for _ in range(2):                                   # a second epoch starts from a clean slate
        targets = np.concatenate([t for _, t in loader])
        if training:
            assert len(targets) == n // batch * batch and len(set(targets.tolist())) == len(targets)
        else:
            assert targets.tolist() == list(range(n))


def test_the_folder_reader_hands_over_the_whole_file_in_memory(image_root):
    """One read a file, closed before the decoder sees it: nothing of the
    sample's decode goes back to the file system."""
    import io
    ds = create_dataset('', root=image_root, split='train')
    fobj, target = ds.reader[3]
    path = ds.reader.samples[3][0]
    assert isinstance(fobj, io.BytesIO) and fobj.getvalue() == open(path, 'rb').read()
    assert Image.open(fobj).size == (56, 48) and target == ds.reader.samples[3][1]
