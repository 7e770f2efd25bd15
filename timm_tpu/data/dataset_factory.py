"""Dataset factory (reference: timm/data/dataset_factory.py:63-230).

Name-scheme dispatch: '' / 'folder' → ImageFolder; 'hfds/name' → HuggingFace
map-style datasets (when the library is present). TFDS/WDS schemes raise with
guidance until those readers land.
"""
from __future__ import annotations

import os
from typing import Optional

from .dataset import ImageDataset

__all__ = ['create_dataset']


def _search_split(root: str, split: str) -> str:
    split_name = split.split('[')[0]
    try_root = os.path.join(root, split_name)
    if os.path.exists(try_root):
        return try_root
    def _try(syn):
        p = os.path.join(root, syn)
        return p if os.path.exists(p) else None
    if split_name in ('validation', 'val'):
        for syn in ('val', 'validation', 'eval', 'test'):
            p = _try(syn)
            if p:
                return p
    if split_name == 'train':
        p = _try('training')
        if p:
            return p
    return root


class HfdsWrapper:
    """Map-style HF datasets → (PIL, label) samples."""
    decodes_files = True

    def __init__(self, name, root, split, input_key='image', target_key='label'):
        import datasets as hfds
        split = {'validation': 'validation', 'val': 'validation', 'train': 'train'}.get(split, split)
        self.ds = hfds.load_dataset(name, cache_dir=root or None, split=split)
        self.input_key = input_key
        self.target_key = target_key
        self.transform = None
        self.target_transform = None

    def __len__(self):
        return len(self.ds)

    def __getitem__(self, index):
        item = self.ds[int(index)]
        img = item[self.input_key]
        if img.mode != 'RGB':
            img = img.convert('RGB')
        if self.transform is not None:
            img = self.transform(img)
        target = item.get(self.target_key, -1)
        if self.target_transform is not None:
            target = self.target_transform(target)
        return img, target


def create_dataset(
        name: str = '',
        root: Optional[str] = None,
        split: str = 'validation',
        search_split: bool = True,
        class_map=None,
        is_training: bool = False,
        num_classes: Optional[int] = None,
        input_img_mode: str = 'RGB',
        **kwargs,
):
    """(reference dataset_factory.py:63)."""
    kwargs = {k: v for k, v in kwargs.items() if v is not None}
    name = name or ''
    if name == 'tokens':
        # one flat file of ids a split: <root>/<split>.bin
        from .dataset import TokenWindows
        split_name = split.split('[')[0]
        names = {'train': ('train', 'training'), 'validation': ('validation', 'val', 'eval', 'test')}
        found = [p for p in (os.path.join(root or '', n + '.bin') for n in names.get(split_name, (split_name,)))
                 if os.path.isfile(p)]
        if not found:
            raise FileNotFoundError(f'no {split_name}.bin token file under {root}')
        return TokenWindows(found[0], seq_len=kwargs['seq_len'], vocab_size=num_classes)
    if name.startswith('hfds/'):
        return HfdsWrapper(name[5:], root, split, **{k: kwargs[k] for k in ('input_key', 'target_key') if k in kwargs})
    if name.startswith('wds/'):
        import jax
        from .dataset import IterableImageDataset
        from .readers_streaming import ReaderWds
        reader = ReaderWds(
            root=name[4:] if name[4:] else root,
            split=split,
            is_training=is_training,
            seed=kwargs.get('seed', 42),
            input_img_mode=input_img_mode,
            input_key=kwargs.get('input_key'),
            target_key=kwargs.get('target_key'),
            dist_rank=jax.process_index(),
            dist_num_replicas=jax.process_count(),
        )
        return IterableImageDataset(root, reader=reader)
    if name.startswith('tfds/'):
        import jax
        from .dataset import IterableImageDataset
        from .readers_streaming import ReaderTfds
        reader = ReaderTfds(
            root=root, name=name[5:], split=split, is_training=is_training,
            seed=kwargs.get('seed', 42), input_img_mode=input_img_mode,
            dist_rank=jax.process_index(), dist_num_replicas=jax.process_count(),
        )
        return IterableImageDataset(root, reader=reader)
    if name.startswith('hfids/'):
        import jax

        from .dataset import IterableImageDataset
        from .readers_streaming import ReaderHfids
        reader = ReaderHfids(
            name=name[6:], root=root, split=split, is_training=is_training,
            seed=kwargs.get('seed', 42), input_img_mode=input_img_mode,
            input_key=kwargs.get('input_key', 'image'),
            target_key=kwargs.get('target_key', 'label'),
            dist_rank=jax.process_index(), dist_num_replicas=jax.process_count(),
        )
        return IterableImageDataset(root, reader=reader)
    if name.startswith('torch/'):
        # torchvision dataset schemes (reference dataset_factory.py:63-230);
        # torchvision is an optional dependency here
        try:
            from torchvision import datasets as tv_datasets
        except ImportError as e:
            raise ImportError(
                'torch/ dataset schemes require torchvision, which is not installed') from e
        name = name[6:].lower()
        tv_split = 'train' if is_training or split in ('train', 'training') else 'val'
        _simple = dict(
            cifar10=tv_datasets.CIFAR10, cifar100=tv_datasets.CIFAR100,
            mnist=tv_datasets.MNIST, kmnist=tv_datasets.KMNIST,
            fashion_mnist=tv_datasets.FashionMNIST, qmnist=tv_datasets.QMNIST,
        )
        if name in _simple:
            return _simple[name](root=root, train=tv_split == 'train', download=kwargs.get('download', False))
        if name == 'image_folder' or name == 'folder':
            if search_split and root and os.path.isdir(root):
                root = _search_split(root, split)
            return tv_datasets.ImageFolder(root)
        if name == 'places365':
            return tv_datasets.Places365(
                root=root, split='train-standard' if tv_split == 'train' else 'val',
                download=kwargs.get('download', False))
        if name == 'imagenet':
            return tv_datasets.ImageNet(root=root, split=tv_split)
        raise ValueError(f'Unknown torchvision dataset {name}')
    # tar file(s): map-style reader over image members
    if root and (str(root).endswith('.tar') or name == 'tar'):
        from .readers_streaming import ReaderImageInTar
        reader = ReaderImageInTar(root, class_map=class_map or '', input_img_mode=input_img_mode)
        return ImageDataset(root, reader=reader, split=split, input_img_mode=input_img_mode)
    # folder default
    if search_split and root and os.path.isdir(root):
        root = _search_split(root, split)
    return ImageDataset(
        root, split=split, class_map=class_map or '', input_img_mode=input_img_mode, **kwargs)
