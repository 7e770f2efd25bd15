#!/usr/bin/env python3
"""Checkpoint evaluation script (reference: validate.py:1-571).

Evaluates a model (optionally from checkpoint) on a validation set; outputs
top-1/top-5, loss, throughput; csv/json results; bulk model-list mode.
"""
from __future__ import annotations

import argparse
import csv
import glob
import json
import logging
import os
import time
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

_logger = logging.getLogger('validate')

parser = argparse.ArgumentParser(description='TPU-native ImageNet validation')
parser.add_argument('data', nargs='?', metavar='DIR', const=None, help='path to dataset (positional)')
parser.add_argument('--data-dir', metavar='DIR', help='path to dataset root')
parser.add_argument('--dataset', metavar='NAME', default='')
parser.add_argument('--split', metavar='NAME', default='validation')
parser.add_argument('--model', '-m', metavar='NAME', default='vit_tiny_patch16_224')
parser.add_argument('--pretrained', dest='pretrained', action='store_true')
parser.add_argument('--checkpoint', default='', type=str, metavar='PATH')
parser.add_argument('--use-ema', dest='use_ema', action='store_true')
parser.add_argument('-b', '--batch-size', default=256, type=int, metavar='N')
parser.add_argument('--img-size', default=None, type=int, metavar='N')
parser.add_argument('--device', default=None, type=str,
                    help="jax platform override (e.g. 'cpu'); must be set before first device op")
parser.add_argument('--input-size', default=None, nargs=3, type=int, metavar='N N N')
parser.add_argument('--crop-pct', default=None, type=float, metavar='N')
parser.add_argument('--crop-mode', default=None, type=str, metavar='N')
parser.add_argument('--mean', type=float, nargs='+', default=None, metavar='MEAN')
parser.add_argument('--std', type=float, nargs='+', default=None, metavar='STD')
parser.add_argument('--interpolation', default='', type=str, metavar='NAME')
parser.add_argument('--num-classes', type=int, default=None)
parser.add_argument('--class-map', default='', type=str, metavar='FILENAME')
parser.add_argument('-j', '--workers', default=4, type=int, metavar='N')
parser.add_argument('--log-freq', default=20, type=int, metavar='N')
parser.add_argument('--amp', action='store_true', default=False, help='bf16 compute')
parser.add_argument('--test-pool', dest='test_pool', action='store_true',
                    help='(not yet supported; warns if set)')
parser.add_argument('--real-labels', default='', type=str, metavar='FILENAME',
                    help='ImageNet-Real labels json for relabeled eval')
parser.add_argument('--results-file', default='', type=str, metavar='FILENAME')
parser.add_argument('--results-format', default='csv', type=str)
parser.add_argument('--model-list', default='', type=str, metavar='FILENAME or WILDCARD',
                    help='evaluate a list/wildcard of models in sequence')
parser.add_argument('--retry', default=False, action='store_true',
                    help='halve batch size and retry on resource exhaustion')
parser.add_argument('--block-scan', action='store_true', default=False,
                    help='scan-over-layers block execution (O(1)-in-depth trace/compile)')
parser.add_argument('--device-prefetch', type=int, default=0, metavar='N',
                    help='keep N batches in flight on device while the step runs; 0 disables')
parser.add_argument('--quantize', default='', type=str, choices=['', 'int8'],
                    help='post-training weight-only quantization of the eval forward '
                         '(serve-path parity): int8 per-output-channel symmetric scales, '
                         'dequantized at use inside the jitted step')
parser.add_argument('--quant-top1-delta', default=0.5, type=float, metavar='PCT',
                    help='with --quantize: also run the fp32 arm on every batch (same data '
                         'pass) and fail if quantized top-1 drops more than this many '
                         'points below fp32; <= 0 skips the fp32 arm and the gate')
parser.add_argument('--fsdp', type=int, default=0, metavar='N',
                    help="shard model weights over an N-way 'fsdp' mesh axis for eval "
                         '(fits models larger than one chip HBM); 0 disables')
parser.add_argument('--tp', type=int, default=0, metavar='N',
                    help="tensor parallelism for eval: shard attention heads + MLP hidden "
                         "over an N-way 'model' mesh axis (composes with --fsdp); 0 disables")


def validate(args):
    import timm_tpu
    from timm_tpu.data import create_dataset, create_loader, resolve_data_config
    from timm_tpu.models import load_checkpoint
    from timm_tpu.parallel import create_mesh, set_global_mesh, shard_batch
    from timm_tpu.utils import AverageMeter

    if args.device:
        # an explicit choice of platform; must land before the first device op
        jax.config.update('jax_platforms', args.device)
    from timm_tpu.utils import configure_compile_cache
    configure_compile_cache()
    mesh = create_mesh(fsdp=args.fsdp if args.fsdp else None,
                       tp=args.tp if args.tp else None)
    set_global_mesh(mesh)

    dtype = jnp.bfloat16 if args.amp else None
    try:
        model = timm_tpu.create_model(
            args.model,
            pretrained=args.pretrained,
            num_classes=args.num_classes,
            img_size=args.img_size,
            dtype=dtype,
        )
    except TypeError:
        # conv archs take no img_size; it still drives the data config below
        model = timm_tpu.create_model(
            args.model, pretrained=args.pretrained, num_classes=args.num_classes, dtype=dtype)
    num_classes = args.num_classes or model.num_classes
    if args.checkpoint:
        load_checkpoint(model, args.checkpoint, use_ema=args.use_ema)
    if args.block_scan:
        if hasattr(model, 'set_block_scan'):
            model.set_block_scan(True)
        else:
            _logger.warning(f'--block-scan: {args.model} has no scannable block stack; ignored')
    model.eval()

    data_config = resolve_data_config(vars(args), model=model)
    from timm_tpu.models import model_state_dict
    param_count = sum(v.size for v in model_state_dict(model, include_stats=False).values())
    _logger.info(f'Model {args.model} created, param count: {param_count/1e6:.1f}M')

    test_time_pool = False
    if args.test_pool:
        from timm_tpu.layers import apply_test_time_pool
        model, test_time_pool = apply_test_time_pool(model, data_config)
        if test_time_pool:
            data_config['crop_pct'] = 1.0  # full-image input for TTA pooling
        else:
            _logger.info('--test-pool requested but eval size does not exceed the '
                         'pretrained default; using the standard head')

    root = args.data_dir or args.data
    dataset = create_dataset(
        args.dataset, root=root, split=args.split, class_map=args.class_map)
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

    real_labels = None
    if args.real_labels:
        from timm_tpu.data import RealLabelsImagenet
        real_labels = RealLabelsImagenet(
            dataset.filenames(basename=True), real_json=args.real_labels)

    from flax import nnx
    graphdef, state = nnx.split(model)
    if 'fsdp' in mesh.axis_names or 'model' in mesh.axis_names:
        # large weights shard over 'fsdp'/'model' (path-rule placement); XLA
        # gathers/keeps shards as the constraints dictate, so eval fits models
        # larger than one chip's HBM
        from timm_tpu.parallel import build_param_shardings
        state = jax.device_put(state, build_param_shardings(state, mesh))
    mean = jnp.asarray(data_config['mean'], jnp.float32).reshape(1, 1, 1, -1)
    std = jnp.asarray(data_config['std'], jnp.float32).reshape(1, 1, 1, -1)

    def make_eval_step(to_dense):
        @jax.jit
        def eval_step(state, x, target, valid):
            x = (x - mean) / std
            if dtype is not None:
                x = x.astype(dtype)
            logits = nnx.merge(graphdef, to_dense(state))(x).astype(jnp.float32)
            logprobs = jax.nn.log_softmax(logits, axis=-1)
            w = valid.astype(jnp.float32)
            denom = jnp.maximum(w.sum(), 1.0)
            loss = -(jnp.take_along_axis(logprobs, target[:, None], axis=-1)[:, 0] * w).sum() / denom
            top = jnp.argsort(logits, axis=-1)[:, -5:]
            acc1 = ((top[:, -1] == target) * w).sum() / denom * 100.0
            acc5 = ((top == target[:, None]).any(axis=-1) * w).sum() / denom * 100.0
            return loss, acc1, acc5, top[:, ::-1]  # top-5 preds, best first
        return eval_step

    # quantize-then-validate: the primary arm evaluates the int8 weights
    # (dequantized at use inside the jit, exactly the serve-path program);
    # the gate arm reruns fp32 on the SAME batches so the top-1 delta is a
    # single-pass paired comparison, not two dataset traversals
    eval_step_fp32 = None
    if args.quantize:
        from timm_tpu.quantize import dequantize_tree, quantize_tree
        eval_state = quantize_tree(state)
        if 'fsdp' in mesh.axis_names or 'model' in mesh.axis_names:
            from timm_tpu.parallel import build_quant_shardings
            eval_state = jax.device_put(
                eval_state, build_quant_shardings(eval_state, mesh))
        eval_step = make_eval_step(dequantize_tree)
        if args.quant_top1_delta > 0:
            eval_step_fp32 = make_eval_step(lambda s: s)
        _logger.info(f'Quantized weights to {args.quantize} for eval'
                     + ('' if eval_step_fp32 is None else
                        f' (fp32 gate arm on, max top-1 delta {args.quant_top1_delta})'))
    else:
        eval_state = state
        eval_step = make_eval_step(lambda s: s)

    # one bucket shape for the whole eval: batch_size rounded up to the mesh
    # shard count. The final partial batch pads up to the SAME shape as every
    # other batch (masked slots), so the loop compiles exactly one executable
    # instead of paying a fresh XLA compile for the odd-sized last batch.
    from timm_tpu.serve import batch_bucket, pad_rows
    bucket = batch_bucket(args.batch_size, mesh.size)

    loss_m, top1_m, top5_m, time_m = AverageMeter(), AverageMeter(), AverageMeter(), AverageMeter()
    top1_fp32_m = AverageMeter()
    end = time.time()
    try:
        for batch_idx, (x_np, t_np) in enumerate(loader):
            n = x_np.shape[0]
            x_np, t_np, valid_np = pad_rows(np.asarray(x_np), bucket, np.asarray(t_np))
            batch = shard_batch({'x': jnp.asarray(x_np), 't': jnp.asarray(t_np),
                                 'v': jnp.asarray(valid_np)}, mesh)
            loss, acc1, acc5, topk = eval_step(eval_state, batch['x'], batch['t'], batch['v'])
            if eval_step_fp32 is not None:
                _, ref1, _, _ = eval_step_fp32(state, batch['x'], batch['t'], batch['v'])
                top1_fp32_m.update(float(ref1), n)
            if real_labels is not None:
                real_labels.add_result(np.asarray(topk)[:n], is_topk=True)  # drop pad rows
            loss_m.update(float(loss), n)
            top1_m.update(float(acc1), n)
            top5_m.update(float(acc5), n)
            time_m.update(time.time() - end)
            end = time.time()
            if batch_idx % args.log_freq == 0:
                _logger.info(
                    f'Test: [{batch_idx:>4d}/{len(loader)}]  '
                    f'Time: {time_m.val:.3f}s ({n / max(time_m.avg, 1e-9):>7.1f}/s)  '
                    f'Loss: {loss_m.val:>7.4f} ({loss_m.avg:>6.4f})  '
                    f'Acc@1: {top1_m.val:>7.3f} ({top1_m.avg:>7.3f})  '
                    f'Acc@5: {top5_m.val:>7.3f} ({top5_m.avg:>7.3f})')
    finally:
        getattr(loader, 'close', lambda: None)()   # the decode processes end here, on an exception too

    if real_labels is not None:
        # replace top-1/5 with the relabeled scores (reference validate.py:418)
        top1_m.avg = real_labels.get_accuracy(k=1)
        top5_m.avg = real_labels.get_accuracy(k=5)
    results = OrderedDict(
        model=args.model,
        top1=round(top1_m.avg, 4), top1_err=round(100 - top1_m.avg, 4),
        top5=round(top5_m.avg, 4), top5_err=round(100 - top5_m.avg, 4),
        param_count=round(param_count / 1e6, 2),
        img_size=data_config['input_size'][-1],
        crop_pct=data_config['crop_pct'],
        interpolation=data_config['interpolation'],
    )
    if args.quantize:
        results['quantize'] = args.quantize
    _logger.info(' * Acc@1 {:.3f} ({:.3f}) Acc@5 {:.3f} ({:.3f})'.format(
        results['top1'], results['top1_err'], results['top5'], results['top5_err']))
    if eval_step_fp32 is not None:
        delta = top1_fp32_m.avg - top1_m.avg
        results['top1_fp32'] = round(top1_fp32_m.avg, 4)
        results['quant_top1_delta'] = round(delta, 4)
        _logger.info(f' * Quant gate: fp32 Acc@1 {top1_fp32_m.avg:.3f}, '
                     f'{args.quantize} Acc@1 {top1_m.avg:.3f}, delta {delta:+.4f} '
                     f'(max allowed {args.quant_top1_delta})')
        if delta > args.quant_top1_delta:
            raise RuntimeError(
                f'quantize-then-validate gate failed: {args.quantize} top-1 '
                f'{top1_m.avg:.4f} is {delta:.4f} points below fp32 '
                f'{top1_fp32_m.avg:.4f} (max allowed {args.quant_top1_delta})')
    return results


def main():
    from timm_tpu.models import is_model, list_models
    from timm_tpu.utils import setup_default_logging
    setup_default_logging()
    args = parser.parse_args()

    model_names = []
    if args.model_list:
        if os.path.exists(args.model_list):
            with open(args.model_list) as f:
                model_names = [line.strip() for line in f if line.strip()]
        else:
            model_names = list_models(args.model_list)
    def _validate_with_retry(args):
        """Batch-size decay retry (reference utils/decay_batch.py:8-43)."""
        batch_size = args.batch_size
        while batch_size >= 1:
            args.batch_size = batch_size
            try:
                return validate(args)
            except Exception as e:
                if args.retry and 'RESOURCE_EXHAUSTED' in str(e).upper() and batch_size > 1:
                    batch_size = max(1, batch_size // 2)
                    _logger.warning(f'OOM, retrying with batch size {batch_size}')
                    continue
                raise

    results = []
    if model_names:
        orig_batch = args.batch_size
        for name in model_names:
            args.model = name
            args.batch_size = orig_batch
            try:
                r = _validate_with_retry(args)
            except Exception as e:
                _logger.error(f'{name} failed: {e}')
                continue
            results.append(r)
        results = sorted(results, key=lambda x: x['top1'], reverse=True)
    else:
        results = [_validate_with_retry(args)]

    if args.results_file:
        if args.results_format == 'json':
            with open(args.results_file, 'w') as f:  # timm-tpu-lint: disable=process-zero-io single-process evaluation driver; no pod launch path
                json.dump(results, f, indent=2)
        else:
            with open(args.results_file, 'w') as f:  # timm-tpu-lint: disable=process-zero-io single-process evaluation driver; no pod launch path
                dw = csv.DictWriter(f, fieldnames=results[0].keys())
                dw.writeheader()
                for r in results:
                    dw.writerow(r)
    print(f'--result\n{json.dumps(results if len(results) > 1 else results[0], indent=4)}')


if __name__ == '__main__':
    main()
