#!/usr/bin/env python3
"""Model benchmark: inference/train step time + FLOP profile
(reference: benchmark.py:1-692 — same CSV schema: samples_per_sec, step_time,
batch_size, img_size, param_count, gmacs).

Timing fuses K steps into one XLA program (lax.scan) so results are device
time, analogous to the reference's CUDA-event timing (benchmark.py:149-157).
GMACs come from the compiled HLO cost analysis in place of the reference's
deepspeed/fvcore profilers (benchmark.py:181-204).
"""
from __future__ import annotations

import argparse
import csv as csv_mod
import json
import logging
import time
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

_logger = logging.getLogger('benchmark')

parser = argparse.ArgumentParser(description='TPU-native model benchmark')
parser.add_argument('--model-list', metavar='NAME', default='', help='txt file or wildcard of models')
parser.add_argument('--model', '-m', metavar='NAME', default='resnet50')
parser.add_argument('--bench', default='infer', type=str,
                    help="('infer', 'train', 'both', 'profile')")
parser.add_argument('-b', '--batch-size', default=256, type=int)
parser.add_argument('--img-size', default=None, type=int)
parser.add_argument('--num-warm-iter', default=2, type=int)
parser.add_argument('--num-bench-iter', default=10, type=int)
parser.add_argument('--amp', action='store_true', default=True)
parser.add_argument('--no-amp', dest='amp', action='store_false')
parser.add_argument('--precision', default='', type=str, help='bfloat16|float32 (overrides --amp)')
parser.add_argument('--num-classes', type=int, default=None)
parser.add_argument('--opt', default='sgd', type=str)
parser.add_argument('--results-file', default='', type=str)
parser.add_argument('--results-format', default='csv', type=str)


def _resolve_img_size(model, args):
    if args.img_size:
        return args.img_size
    if hasattr(model, 'pretrained_cfg'):
        return model.pretrained_cfg.input_size[-1]
    return 224


def benchmark_model(model_name: str, args) -> OrderedDict:
    import optax
    from flax import nnx
    import timm_tpu
    from timm_tpu.loss import cross_entropy
    from timm_tpu.models import model_state_dict
    from timm_tpu.optim import create_optimizer_v2

    precision = args.precision or ('bfloat16' if args.amp else 'float32')
    dtype = jnp.bfloat16 if precision == 'bfloat16' else None

    model = timm_tpu.create_model(model_name, num_classes=args.num_classes, dtype=dtype)
    img_size = _resolve_img_size(model, args)
    param_count = sum(v.size for v in model_state_dict(model, include_stats=False).values())
    B, K = args.batch_size, args.num_bench_iter

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(B, img_size, img_size, 3),
                    jnp.bfloat16 if dtype is not None else jnp.float32)

    results = OrderedDict(
        model=model_name,
        batch_size=B,
        img_size=img_size,
        precision=precision,
        param_count=round(param_count / 1e6, 2),
    )

    model.eval()
    graphdef_e, state_e = nnx.split(model)

    @jax.jit
    def multi_fwd(state, x):
        def body(c, _):
            out = nnx.merge(graphdef_e, state)(x + c * 0)
            return out.mean().astype(x.dtype), ()
        return jax.lax.scan(body, jnp.zeros((), x.dtype), None, length=K)[0]

    # GMACs from compiled forward cost analysis
    try:
        fwd_flops = jax.jit(lambda s, xx: nnx.merge(graphdef_e, s)(xx)).lower(
            state_e, x).compile().cost_analysis().get('flops', 0)
        results['gmacs'] = round(fwd_flops / 2 / B / 1e9, 2)
    except Exception:
        results['gmacs'] = None

    if args.bench in ('infer', 'both', 'profile'):
        for _ in range(max(1, args.num_warm_iter)):
            float(multi_fwd(state_e, x))
        t0 = time.perf_counter()
        float(multi_fwd(state_e, x))
        dt = (time.perf_counter() - t0) / K
        results['infer_samples_per_sec'] = round(B / dt, 2)
        results['infer_step_time'] = round(dt * 1000, 3)

    if args.bench in ('train', 'both'):
        model.train()
        opt = create_optimizer_v2(model, opt=args.opt, lr=1e-4)
        graphdef_t, params, rest = nnx.split(model, nnx.Param, ...)
        opt_state = opt.init(params)
        t = jnp.asarray(rng.randint(0, model.num_classes, B))

        @jax.jit
        def multi_train(params, opt_state, x, t):
            def body(carry, _):
                params, opt_state = carry

                def loss_fn(p):
                    return cross_entropy(nnx.merge(graphdef_t, p, rest, copy=True)(x), t)
                loss, grads = jax.value_and_grad(loss_fn)(params)
                updates, opt_state = opt.update(grads, opt_state, params, lr=1e-4)
                return (optax.apply_updates(params, updates), opt_state), loss
            (_, _), losses = jax.lax.scan(body, (params, opt_state), None, length=K)
            return losses[-1]

        for _ in range(max(1, args.num_warm_iter)):
            float(multi_train(params, opt_state, x, t))
        t0 = time.perf_counter()
        float(multi_train(params, opt_state, x, t))
        dt = (time.perf_counter() - t0) / K
        results['train_samples_per_sec'] = round(B / dt, 2)
        results['train_step_time'] = round(dt * 1000, 3)

    # reference-compatible alias columns
    if 'infer_samples_per_sec' in results:
        results['samples_per_sec'] = results['infer_samples_per_sec']
        results['step_time'] = results['infer_step_time']
    elif 'train_samples_per_sec' in results:
        results['samples_per_sec'] = results['train_samples_per_sec']
        results['step_time'] = results['train_step_time']
    return results


def main():
    import os
    from timm_tpu.models import list_models
    from timm_tpu.utils import setup_default_logging
    setup_default_logging()
    args = parser.parse_args()

    model_names = [args.model]
    if args.model_list:
        if os.path.exists(args.model_list):
            with open(args.model_list) as f:
                model_names = [l.strip() for l in f if l.strip()]
        else:
            model_names = list_models(args.model_list)

    results = []
    for name in model_names:
        try:
            r = benchmark_model(name, args)
            _logger.info(json.dumps(r))
            results.append(r)
        except Exception as e:
            _logger.error(f'{name} failed: {e}')

    if args.results_file and results:
        if args.results_format == 'json':
            with open(args.results_file, 'w') as f:  # timm-tpu-lint: disable=process-zero-io single-process benchmark driver; no pod launch path
                json.dump(results, f, indent=2)
        else:
            keys = max(results, key=len).keys()
            with open(args.results_file, 'w') as f:  # timm-tpu-lint: disable=process-zero-io single-process benchmark driver; no pod launch path
                dw = csv_mod.DictWriter(f, fieldnames=keys)
                dw.writeheader()
                for r in results:
                    dw.writerow(r)
    print(json.dumps(results if len(results) > 1 else results[0], indent=2))


if __name__ == '__main__':
    main()
