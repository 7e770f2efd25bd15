#!/usr/bin/env python3
"""Folder inference → top-k predictions to csv/json/parquet
(reference: inference.py:1-389).
"""
from __future__ import annotations

import argparse
import json
import logging
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

_logger = logging.getLogger('inference')

parser = argparse.ArgumentParser(description='TPU-native inference')
parser.add_argument('data', nargs='?', metavar='DIR', const=None)
parser.add_argument('--data-dir', metavar='DIR')
parser.add_argument('--dataset', metavar='NAME', default='')
parser.add_argument('--split', metavar='NAME', default='validation')
parser.add_argument('--model', '-m', metavar='NAME', default='vit_tiny_patch16_224')
parser.add_argument('--pretrained', action='store_true')
parser.add_argument('--checkpoint', default='', type=str, metavar='PATH')
parser.add_argument('--use-ema', action='store_true')
parser.add_argument('-b', '--batch-size', default=256, type=int)
parser.add_argument('--img-size', default=None, type=int)
parser.add_argument('--input-size', default=None, nargs=3, type=int)
parser.add_argument('--crop-pct', default=None, type=float)
parser.add_argument('--crop-mode', default=None, type=str)
parser.add_argument('--num-classes', type=int, default=None)
parser.add_argument('--class-map', default='', type=str)
parser.add_argument('--label-type', default='index', type=str,
                    choices=['index', 'name', 'description', 'detail'],
                    help="'name'/'description' resolve ImageNet synsets/lemmas from bundled "
                         'class metadata (falling back to dataset class-folder names)')
parser.add_argument('-j', '--workers', default=4, type=int)
parser.add_argument('--amp', action='store_true', default=False)
parser.add_argument('--device', default=None, type=str,
                    help="jax platform override (e.g. 'cpu'); must be set before first device op")
parser.add_argument('--topk', default=1, type=int, metavar='N')
parser.add_argument('--fullname', action='store_true', default=False)
parser.add_argument('--outputs-name', default=None)
parser.add_argument('--output-dir', default=None)
parser.add_argument('--output-type', default='csv', choices=['csv', 'json', 'parquet'])
parser.add_argument('--filename-col', default='filename')
parser.add_argument('--block-scan', action='store_true', default=False,
                    help='scan-over-layers block execution (O(1)-in-depth trace/compile)')
parser.add_argument('--device-prefetch', type=int, default=0, metavar='N',
                    help='keep N batches in flight on device while the step runs; 0 disables')


def main():
    import timm_tpu
    from timm_tpu.data import create_dataset, create_loader, resolve_data_config
    from timm_tpu.models import load_checkpoint
    from timm_tpu.utils import setup_default_logging
    from flax import nnx

    setup_default_logging()
    args = parser.parse_args()

    if args.device:
        # an explicit choice of platform; must land before the first device op
        # (model init)
        jax.config.update('jax_platforms', args.device)
    from timm_tpu.utils import configure_compile_cache
    configure_compile_cache()
    dtype = jnp.bfloat16 if args.amp else None
    try:
        model = timm_tpu.create_model(
            args.model, pretrained=args.pretrained, num_classes=args.num_classes,
            img_size=args.img_size, dtype=dtype)
    except TypeError:
        model = timm_tpu.create_model(
            args.model, pretrained=args.pretrained, num_classes=args.num_classes, dtype=dtype)
    if args.checkpoint:
        load_checkpoint(model, args.checkpoint, use_ema=args.use_ema)
    if args.block_scan:
        if hasattr(model, 'set_block_scan'):
            model.set_block_scan(True)
        else:
            _logger.warning(f'--block-scan: {args.model} has no scannable block stack; ignored')
    model.eval()

    data_config = resolve_data_config(vars(args), model=model)
    root = args.data_dir or args.data
    dataset = create_dataset(args.dataset, root=root, split=args.split, class_map=args.class_map)
    loader = create_loader(
        dataset,
        input_size=data_config['input_size'],
        batch_size=args.batch_size,
        interpolation=data_config['interpolation'],
        mean=data_config['mean'],
        std=data_config['std'],
        num_workers=args.workers,
        crop_pct=data_config['crop_pct'],
        crop_mode=data_config['crop_mode'],
        device_prefetch=args.device_prefetch,
    )

    graphdef, state = nnx.split(model)
    mean = jnp.asarray(data_config['mean'], jnp.float32).reshape(1, 1, 1, -1)
    std = jnp.asarray(data_config['std'], jnp.float32).reshape(1, 1, 1, -1)
    k = min(args.topk, args.num_classes or model.num_classes)

    @jax.jit
    def infer_step(state, x):
        x = (x - mean) / std
        if dtype is not None:
            x = x.astype(dtype)
        logits = nnx.merge(graphdef, state)(x).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        order = jnp.argsort(probs, axis=-1)[:, ::-1][:, :k]
        top_probs = jnp.take_along_axis(probs, order, axis=-1)
        return order, top_probs

    # every batch — including the final partial one — runs at one padded
    # bucket shape, so the whole loop uses a single compiled executable
    # instead of paying a fresh XLA compile for the odd-sized last batch
    from timm_tpu.serve import batch_bucket, pad_rows, strip_rows
    bucket = batch_bucket(args.batch_size)

    all_indices, all_probs = [], []
    t0 = time.time()
    try:
        for x_np, _ in loader:
            n = int(x_np.shape[0])
            if n != bucket:  # partial final batch: pad up to the bucket shape
                x_np, _valid = pad_rows(np.asarray(x_np), bucket)
            idx, prb = strip_rows(infer_step(state, jnp.asarray(x_np)), n)
            all_indices.append(np.asarray(idx))
            all_probs.append(np.asarray(prb))
    finally:
        getattr(loader, 'close', lambda: None)()   # the decode processes end here, on an exception too

    if not all_indices:
        raise RuntimeError(f'No images found for inference under {root!r} (split {args.split!r})')
    num = sum(a.shape[0] for a in all_indices)
    _logger.info(f'Inference complete: {num} images in {time.time() - t0:.1f}s')

    indices = np.concatenate(all_indices)
    probs = np.concatenate(all_probs)
    filenames = dataset.filenames(basename=not args.fullname)[:num]

    to_label = None
    if args.label_type in ('name', 'description', 'detail'):
        # prefer the model's ImageNet label space (reference inference.py:213)
        from timm_tpu.data.dataset_info import ImageNetInfo, infer_imagenet_subset
        subset = infer_imagenet_subset({'num_classes': args.num_classes or model.num_classes})
        if subset is not None:
            info = ImageNetInfo(subset)
            if args.label_type == 'name':
                to_label = info.index_to_label_name
            else:
                from functools import partial
                to_label = partial(info.index_to_description, detailed=args.label_type == 'detail')
        elif hasattr(dataset, 'reader') and hasattr(dataset.reader, 'class_to_idx'):
            idx_to_name = {v: k for k, v in dataset.reader.class_to_idx.items()}
            to_label = lambda i: idx_to_name.get(i, i)

    def _label(i: int):
        return to_label(int(i)) if to_label is not None else int(i)

    rows = []
    for fn, ind, prb in zip(filenames, indices, probs):
        row = {args.filename_col: fn}
        if k == 1:
            row['label'] = _label(int(ind[0]))
            row['prob'] = float(prb[0])
        else:
            for j in range(k):
                row[f'label_{j}'] = _label(int(ind[j]))
                row[f'prob_{j}'] = float(prb[j])
        rows.append(row)

    out_dir = args.output_dir or '.'
    os.makedirs(out_dir, exist_ok=True)
    base = os.path.join(out_dir, args.outputs_name or f'{args.model}-results')
    if args.output_type == 'json':
        with open(base + '.json', 'w') as f:  # timm-tpu-lint: disable=process-zero-io single-process inference driver; no pod launch path
            json.dump(rows, f, indent=2)
    elif args.output_type == 'parquet':
        import pandas as pd
        pd.DataFrame(rows).set_index(args.filename_col).to_parquet(base + '.parquet')
    else:
        import csv
        with open(base + '.csv', 'w') as f:  # timm-tpu-lint: disable=process-zero-io single-process inference driver; no pod launch path
            dw = csv.DictWriter(f, fieldnames=rows[0].keys())
            dw.writeheader()
            for r in rows:
                dw.writerow(r)
    _logger.info(f'Wrote results to {base}.{args.output_type}')


if __name__ == '__main__':
    main()
