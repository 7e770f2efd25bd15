#!/usr/bin/env python3
"""ImageNet-style training script, TPU-native.

Re-designed from the reference train.py (1533 LoC) for JAX: one jitted train
step over a data-parallel mesh; host-side scheduler; bf16 compute via --amp.
Flag names mirror the reference where the concept carries over
(reference: train.py:71-475 argparse, :487 main, :1231 train_one_epoch).
"""
from __future__ import annotations

import argparse
import gc
import json
import logging
import os
import sys
import time
from collections import OrderedDict
from datetime import datetime
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import yaml

_logger = logging.getLogger('train')

# a model's `task_kind` -> (its task in `timm_tpu.task`, its feed): an image model has no `task_kind`
TASK_KINDS = {None: ('ClassificationTask', 'images'),
              'causal_lm': ('CausalLMTask', 'tokens'),
              'block_diffusion_lm': ('BlockDiffusionLMTask', 'tokens')}
TOKEN_KINDS = tuple(kind for kind, (_, feed) in TASK_KINDS.items() if feed == 'tokens')


def make_parser():
    parser = argparse.ArgumentParser(description='TPU-native training')
    # dataset
    group = parser.add_argument_group('Dataset parameters')
    group.add_argument('--data-dir', metavar='DIR', default=None, help='path to dataset root')
    group.add_argument('--dataset', metavar='NAME', default='', help='dataset type/scheme')
    group.add_argument('--train-split', metavar='NAME', default='train')
    group.add_argument('--val-split', metavar='NAME', default='validation')
    group.add_argument('--seq-len', type=int, default=2048, metavar='N',
                       help="tokens a sequence for --dataset tokens (<data-dir>/train.bin, raw int32 ids)")
    group.add_argument('--synthetic-data', action='store_true',
                       help='use an on-the-fly synthetic dataset (no --data-dir needed)')
    group.add_argument('--num-classes', type=int, default=None)
    group.add_argument('--class-map', default='', type=str)
    # model
    group = parser.add_argument_group('Model parameters')
    group.add_argument('--model', default='vit_tiny_patch16_224', type=str, metavar='MODEL')
    group.add_argument('--pretrained', action='store_true', default=False)
    group.add_argument('--initial-checkpoint', default='', type=str, metavar='PATH')
    group.add_argument('--resume', default='', type=str, metavar='PATH',
                       help="checkpoint to resume from, or 'auto' to pick the newest valid "
                            "checkpoint/recovery file in the experiment dir (use with --experiment)")
    group.add_argument('--no-resume-opt', action='store_true', default=False)
    group.add_argument('--img-size', type=int, default=None, metavar='N')
    group.add_argument('--in-chans', type=int, default=None, metavar='N')
    group.add_argument('--input-size', default=None, nargs=3, type=int, metavar='N N N')
    group.add_argument('--mean', type=float, nargs='+', default=None, metavar='MEAN')
    group.add_argument('--std', type=float, nargs='+', default=None, metavar='STD')
    group.add_argument('--interpolation', default='', type=str, metavar='NAME')
    group.add_argument('-b', '--batch-size', type=int, default=128, metavar='N')
    group.add_argument('-vb', '--validation-batch-size', type=int, default=None, metavar='N')
    group.add_argument('--model-kwargs', nargs='*', default={}, action=ParseKwargs)
    group.add_argument('--drop', type=float, default=0.0, metavar='PCT')
    group.add_argument('--drop-path', type=float, default=None, metavar='PCT')
    group.add_argument('--grad-accum-steps', type=int, default=1, metavar='N')
    group.add_argument('--grad-checkpointing', action='store_true', default=False)
    group.add_argument('--block-scan', action='store_true', default=False,
                       help='run homogeneous transformer block stacks as one lax.scan '
                            'over stacked per-layer params (O(1)-in-depth trace/compile)')
    group.add_argument('--distill', default='', type=str, metavar='SPEC',
                       help="knowledge-distillation spec "
                            "'teacher=NAME[,kind=logit|feature][,alpha=F][,temperature=F]"
                            "[,feat_loss=cosine|mse][,checkpoint=PATH]': fine-tune the "
                            'student against a frozen teacher running inside the same '
                            'jitted donated train step (big-teacher -> small-student on '
                            'the mesh); the distill-to-serve recipe pairs this with '
                            'validate.py --quantize int8')
    group.add_argument('--device-prefetch', type=int, default=0, metavar='N',
                       help='keep N batches in flight on device (async host->device '
                            'transfer overlapped with the step); 0 disables')
    group.add_argument('--device-augment', action='store_true', default=False,
                       help='run normalize + mixup/cutmix + random-erase as one donated '
                            'jitted on-device program per batch shape; the host collates '
                            'raw uint8 (or [0,1] NaFlex patches) and only samples augment '
                            'parameters. Requires --grad-accum-steps 1 and a real dataset')
    group.add_argument('--naflex-bucket-mode', type=str, default='budget',
                       choices=('budget', 'native'),
                       help='NaFlex seq-len assignment: "budget" schedules random ladder '
                            'buckets per batch; "native" puts each image in the smallest '
                            'bucket holding its natural grid (single-process only)')
    group.add_argument('--fsdp', type=int, default=0, metavar='N',
                       help="shard params + optimizer state over an N-way 'fsdp' mesh axis "
                            '(ZeRO-style; batch still shards over all devices). N must '
                            'divide the per-slice device count; 0 disables '
                            '(env TIMM_TPU_FSDP is the fallback default)')
    group.add_argument('--tp', type=int, default=0, metavar='N',
                       help="tensor parallelism: shard attention heads + MLP hidden over an "
                            "N-way 'model' mesh axis (Megatron split) with activation "
                            'sharding constraints on the residual stream. Composes with '
                            '--fsdp (fsdp*tp must divide the per-slice device count); '
                            '0 disables (env TIMM_TPU_TP is the fallback default)')
    group.add_argument('--autotune', action='store_true', default=False,
                       help='enumerate legal {fsdp x tp x batch x accum x scan x remat} '
                            'configs for the live topology, rank them on the compiled-'
                            'cost roofline, print the table, and apply the winner '
                            'before building the mesh (the global batch '
                            'batch_size * grad_accum_steps is held exactly constant)')
    group.add_argument('--autotune-probe-top-k', type=int, default=0, metavar='K',
                       help="with --autotune: lower the top-K candidates' REAL train "
                            'steps and re-rank the shortlist on their compiled costs '
                            '(K extra compiles; 0 = estimator tier only)')
    group.add_argument('--amp', action='store_true', default=False,
                       help='bf16 compute (the TPU-native AMP)')
    group.add_argument('--amp-dtype', default='bfloat16', type=str)
    group.add_argument('--device', default=None, type=str,
                       help='pin the JAX platform (tpu/cpu); default = auto '
                            '(reference train.py --device)')
    group.add_argument('--distributed', action='store_true', default=False,
                       help='multi-process pod runtime: call jax.distributed.initialize() '
                            'before any device op (coordinator/rank from the cluster env: '
                            'COORDINATOR_ADDRESS / NUM_PROCESSES / PROCESS_ID, or '
                            'auto-detected on TPU pods). Shards the input pipeline by '
                            'process and switches checkpoints to one-shard-file-per-'
                            'process (README "Multi-host training")')
    # optimizer
    group = parser.add_argument_group('Optimizer parameters')
    group.add_argument('--opt', default='sgd', type=str, metavar='OPTIMIZER')
    group.add_argument('--opt-eps', default=None, type=float, metavar='EPSILON')
    group.add_argument('--opt-betas', default=None, type=float, nargs='+', metavar='BETA')
    group.add_argument('--momentum', type=float, default=0.9, metavar='M')
    group.add_argument('--weight-decay', type=float, default=2e-5)
    group.add_argument('--clip-grad', type=float, default=None, metavar='NORM')
    group.add_argument('--clip-mode', type=str, default='norm')
    group.add_argument('--layer-decay', type=float, default=None)
    group.add_argument('--opt-kwargs', nargs='*', default={}, action=ParseKwargs)
    group.add_argument('--opt-caution', action='store_true', default=False)
    # schedule
    group = parser.add_argument_group('Learning rate schedule parameters')
    group.add_argument('--sched', type=str, default='cosine', metavar='SCHEDULER')
    group.add_argument('--sched-on-updates', action='store_true', default=False)
    group.add_argument('--lr', type=float, default=None, metavar='LR')
    group.add_argument('--lr-base', type=float, default=0.1, metavar='LR')
    group.add_argument('--lr-base-size', type=int, default=256, metavar='DIV')
    group.add_argument('--lr-base-scale', type=str, default='', metavar='SCALE')
    group.add_argument('--lr-noise', type=float, nargs='+', default=None, metavar='pct, pct')
    group.add_argument('--lr-noise-pct', type=float, default=0.67, metavar='PERCENT')
    group.add_argument('--lr-noise-std', type=float, default=1.0, metavar='STDDEV')
    group.add_argument('--lr-cycle-mul', type=float, default=1.0, metavar='MULT')
    group.add_argument('--lr-cycle-decay', type=float, default=0.5, metavar='MULT')
    group.add_argument('--lr-cycle-limit', type=int, default=1, metavar='N')
    group.add_argument('--lr-k-decay', type=float, default=1.0)
    group.add_argument('--warmup-lr', type=float, default=1e-5, metavar='LR')
    group.add_argument('--min-lr', type=float, default=0, metavar='LR')
    group.add_argument('--epochs', type=int, default=300, metavar='N')
    group.add_argument('--epoch-size', type=int, default=0, metavar='N',
                       help='samples per epoch when the loader length is unknown (streaming datasets)')
    group.add_argument('--epoch-repeats', type=float, default=0.0, metavar='N')
    group.add_argument('--start-epoch', default=None, type=int, metavar='N')
    group.add_argument('--decay-milestones', default=[90, 180, 270], type=int, nargs='+', metavar='MILESTONES')
    group.add_argument('--decay-epochs', type=float, default=90, metavar='N')
    group.add_argument('--warmup-epochs', type=int, default=5, metavar='N')
    group.add_argument('--warmup-prefix', action='store_true', default=False)
    group.add_argument('--cooldown-epochs', type=int, default=0, metavar='N')
    group.add_argument('--patience-epochs', type=int, default=10, metavar='N')
    group.add_argument('--decay-rate', '--dr', type=float, default=0.1, metavar='RATE')
    # augmentation / regularization (consumed by the data pipeline)
    group = parser.add_argument_group('Augmentation and regularization parameters')
    group.add_argument('--no-aug', action='store_true', default=False)
    group.add_argument('--scale', type=float, nargs='+', default=[0.08, 1.0], metavar='PCT')
    group.add_argument('--ratio', type=float, nargs='+', default=[3. / 4., 4. / 3.], metavar='RATIO')
    group.add_argument('--hflip', type=float, default=0.5)
    group.add_argument('--vflip', type=float, default=0.0)
    group.add_argument('--color-jitter', type=float, default=0.4, metavar='PCT')
    group.add_argument('--aa', type=str, default=None, metavar='NAME')
    group.add_argument('--reprob', type=float, default=0.0, metavar='PCT')
    group.add_argument('--remode', type=str, default='pixel')
    group.add_argument('--recount', type=int, default=1)
    group.add_argument('--mixup', type=float, default=0.0)
    group.add_argument('--cutmix', type=float, default=0.0)
    group.add_argument('--cutmix-minmax', type=float, nargs='+', default=None)
    group.add_argument('--mixup-prob', type=float, default=1.0)
    group.add_argument('--mixup-switch-prob', type=float, default=0.5)
    group.add_argument('--mixup-mode', type=str, default='batch')
    group.add_argument('--mixup-off-epoch', default=0, type=int, metavar='N')
    group.add_argument('--smoothing', type=float, default=0.1)
    group.add_argument('--train-interpolation', type=str, default='random')
    group.add_argument('--bce-loss', action='store_true', default=False)
    group.add_argument('--bce-sum', action='store_true', default=False)
    group.add_argument('--bce-target-thresh', type=float, default=None)
    group.add_argument('--jsd-loss', action='store_true', default=False)
    group.add_argument('--aug-splits', type=int, default=0,
                       help='Number of augmentation splits (AugMix/JSD; 0 or >=2)')
    group.add_argument('--split-bn', action='store_true',
                       help='Use separate BN statistics per augmentation split')
    # ema
    group = parser.add_argument_group('Model EMA parameters')
    group.add_argument('--model-ema', action='store_true', default=False)
    group.add_argument('--model-ema-decay', type=float, default=0.9998)
    group.add_argument('--model-ema-warmup', action='store_true')
    # misc
    group = parser.add_argument_group('Miscellaneous parameters')
    group.add_argument('--seed', type=int, default=42, metavar='S')
    group.add_argument('--worker-seeding', type=str, default='all')
    group.add_argument('--log-interval', type=int, default=50, metavar='N')
    group.add_argument('--recovery-interval', type=int, default=0, metavar='N')
    group.add_argument('--checkpoint-hist', type=int, default=10, metavar='N')
    group.add_argument('-j', '--workers', type=int, default=4, metavar='N')
    group.add_argument('--output', default='', type=str, metavar='PATH')
    group.add_argument('--experiment', default='', type=str, metavar='NAME')
    group.add_argument('--eval-metric', default='top1', type=str, metavar='EVAL_METRIC')
    group.add_argument('--log-wandb', action='store_true', default=False)
    group.add_argument('--synthetic-len', type=int, default=1024,
                       help='samples per epoch for --synthetic-data')
    # fault tolerance (timm_tpu/resilience; README "Fault tolerance")
    group = parser.add_argument_group('Fault tolerance parameters')
    group.add_argument('--fault-inject', default='', type=str, metavar='SPEC',
                       help="arm the fault-injection harness for drills, e.g. "
                            "'truncate_ckpt,nan_grads@12,sigterm@7,io_error%%50,resize@7:4' "
                            "(timm_tpu/resilience/faultinject.py)")
    group.add_argument('--elastic', action='store_true', default=False,
                       help='elastic resume: rebuild the mesh from the LIVE device '
                            'topology (clamping --fsdp/--tp to what still divides it) '
                            'and rescale --batch-size x --grad-accum-steps so the '
                            "interrupted run's global batch stays constant; refuses "
                            'loudly when no integer solution exists. Combine with '
                            '--resume auto after a slice preemption '
                            '(timm_tpu/resilience/elastic.py)')
    group.add_argument('--nonfinite-tolerance', type=int, default=None, metavar='K',
                       help='abort after K consecutive non-finite (NaN/Inf) train steps '
                            '(default: env TIMM_TPU_NONFINITE_TOLERANCE or 3); skipped '
                            'steps commit nothing and are counted in metrics')
    group.add_argument('--no-nonfinite-guard', action='store_true', default=False,
                       help='disable the in-step all-finite check entirely')
    group.add_argument('--nonfinite-rollback', action='store_true', default=False,
                       help='when the non-finite tolerance trips, reload the newest valid '
                            'checkpoint and continue instead of aborting (budget: '
                            'TIMM_TPU_ROLLBACK_BUDGET, default 1)')
    # NaFlex variable-resolution training (reference train.py --naflex-loader)
    group = parser.add_argument_group('NaFlex parameters')
    group.add_argument('--naflex-loader', action='store_true', help='token-budget variable-res training')
    group.add_argument('--naflex-train-seq-lens', type=int, nargs='+', default=[128, 256, 576, 784, 1024])
    group.add_argument('--naflex-max-seq-len', type=int, default=576)
    group.add_argument('--naflex-patch-sizes', type=int, nargs='+', default=None,
                       help='variable patch sizes sampled per train batch (e.g. 8 12 16)')
    return parser


class ParseKwargs(argparse.Action):
    def __call__(self, parser, namespace, values, option_string=None):
        kw = {}
        for value in values:
            key, _, v = value.partition('=')
            try:
                kw[key] = json.loads(v)
            except json.JSONDecodeError:
                kw[key] = v
        setattr(namespace, self.dest, kw)


def _parse_args(argv=None):
    # two-stage parse: --config YAML sets defaults, CLI overrides (ref train.py:71)
    config_parser = argparse.ArgumentParser(description='Config', add_help=False)
    config_parser.add_argument('-c', '--config', default='', type=str, metavar='FILE')
    args_config, remaining = config_parser.parse_known_args(argv)
    parser = make_parser()
    if args_config.config:
        with open(args_config.config, 'r') as f:
            cfg = yaml.safe_load(f)
            parser.set_defaults(**cfg)
    args = parser.parse_args(remaining)
    args_text = yaml.safe_dump(args.__dict__, default_flow_style=False)
    return args, args_text


def _parse_distill(spec):
    """'teacher=NAME,kind=logit,alpha=0.5,temperature=2.0' -> dict."""
    out = dict(kind='logit', alpha=0.5, temperature=1.0, feat_loss='cosine', checkpoint='')
    for item in filter(None, (s.strip() for s in spec.split(','))):
        if '=' not in item:
            raise ValueError(f"--distill: expected key=value, got {item!r}")
        k, v = item.split('=', 1)
        if k not in ('teacher', 'kind', 'alpha', 'temperature', 'feat_loss', 'checkpoint'):
            raise ValueError(f'--distill: unknown key {k!r}')
        out[k] = float(v) if k in ('alpha', 'temperature') else v
    if 'teacher' not in out:
        raise ValueError("--distill requires teacher=MODEL_NAME")
    if out['kind'] not in ('logit', 'feature'):
        raise ValueError(f"--distill: kind must be logit|feature, got {out['kind']!r}")
    return out


class SyntheticLoader:
    """Deterministic random image/label batches for smoke runs.

    `batch_size` is the GLOBAL batch. Multi-process runs draw the same global
    batch from the seeded stream on every host and each process yields its own
    contiguous row slice, so the union across processes is bit-identical to a
    single-process run — the property the multi-host kill drill asserts on.
    """

    def __init__(self, length, batch_size, img_size, num_classes, seed=0,
                 process_index=0, process_count=1):
        if batch_size % process_count != 0:
            raise ValueError(
                f'synthetic batch size {batch_size} not divisible by '
                f'{process_count} processes')
        self.length = max(1, length // batch_size)
        self.batch_size = batch_size
        self.img_size = img_size
        self.num_classes = num_classes
        self.seed = seed
        self.process_index = process_index
        self.process_count = process_count

    def __len__(self):
        return self.length

    def __iter__(self):
        rng = np.random.RandomState(self.seed)
        local = self.batch_size // self.process_count
        lo = self.process_index * local
        for _ in range(self.length):
            x = rng.rand(self.batch_size, self.img_size, self.img_size, 3).astype(np.float32)
            y = rng.randint(0, self.num_classes, self.batch_size)
            yield x[lo:lo + local], y[lo:lo + local]


def _solver_model_kwargs(args):
    """create_model kwargs for the autotune solver's abstract
    (`nnx.eval_shape`) model build — the pre-mesh surfaces (--autotune, the
    elastic re-solve) run before the real factory_kwargs are assembled."""
    kw = dict(args.model_kwargs)
    if args.num_classes is not None:
        kw.setdefault('num_classes', args.num_classes)
    if args.img_size is not None:
        kw.setdefault('img_size', args.img_size)
    return kw


def _bootstrap_distributed(args):
    """Cluster bring-up for --distributed / pod launches. Must run before ANY
    timm_tpu import: importing the package pulls in flax, which touches the
    XLA backend, and jax.distributed.initialize() refuses to run after the
    first backend touch. init_distributed_device() later detects the already-
    initialized runtime and only fills in args.{world_size,rank,...}."""
    coord = os.environ.get('COORDINATOR_ADDRESS') or os.environ.get('JAX_COORDINATOR_ADDRESS')
    env_cluster = (bool(coord)
                   or int(os.environ.get('SLURM_NTASKS') or 1) > 1
                   or int(os.environ.get('OMPI_COMM_WORLD_SIZE') or 1) > 1)
    if not (getattr(args, 'distributed', False) or env_cluster):
        return
    kwargs = {}
    if coord:
        kwargs['coordinator_address'] = coord
        if os.environ.get('NUM_PROCESSES'):
            kwargs['num_processes'] = int(os.environ['NUM_PROCESSES'])
        if os.environ.get('PROCESS_ID'):
            kwargs['process_id'] = int(os.environ['PROCESS_ID'])
    try:
        jax.distributed.initialize(**kwargs)
        _logger.info(f'Initialized multi-host JAX: process '
                     f'{jax.process_index()}/{jax.process_count()}')
    except Exception:
        if env_cluster:
            raise
        _logger.warning('--distributed requested but no coordinator/cluster '
                        'env detected; continuing single-process')


def main(argv=None):
    """Train from `argv` (default: the process's command line). In-process
    callers (chip_smoke.py) pass the same argument list a shell would."""
    args, args_text = _parse_args(argv)
    if args.device:
        # an explicit choice of platform; must land before the first device op
        jax.config.update('jax_platforms', args.device)
    _bootstrap_distributed(args)

    from timm_tpu import create_model
    from timm_tpu.loss import BinaryCrossEntropy, JsdCrossEntropy, LabelSmoothingCrossEntropy, SoftTargetCrossEntropy
    from timm_tpu.optim import create_optimizer_v2, optimizer_kwargs
    from timm_tpu.parallel import (
        create_mesh, init_distributed_device, is_primary, set_global_mesh, shard_batch,
    )
    from timm_tpu.scheduler import create_scheduler_v2, scheduler_kwargs
    from timm_tpu.utils import (
        AverageMeter, CheckpointSaver, accuracy, get_outdir, random_seed,
        setup_default_logging, tracing, update_summary,
    )

    from timm_tpu.resilience import (
        AsyncCheckpointWriter, GracefulShutdown, NonFiniteError, TrainingPreempted,
        convert_loader_position, load_with_fallback, plan_elastic_resume,
        resolve_auto_resume, restore_host_rng, set_fault_injector,
    )

    setup_default_logging()
    if args.fault_inject:
        set_fault_injector(args.fault_inject)
    world_size, rank, _ = init_distributed_device(args)
    # durable compiles: every process reuses the on-disk XLA executable cache
    # (JAX_COMPILATION_CACHE_DIR; see timm_tpu/utils/compile_cache.py)
    from timm_tpu.utils import configure_compile_cache
    configure_compile_cache()
    random_seed(args.seed, rank)

    if args.elastic:
        # elastic pre-pass: clamp mesh axes to the LIVE topology and hold the
        # interrupted run's global batch constant, BEFORE mesh/loaders exist.
        # (The resume path is re-resolved here because output_dir is built
        # later; `--resume auto` needs --experiment for a stable dir.)
        probe_dir = (os.path.join(args.output or './output/train', args.experiment)
                     if args.experiment else '')
        elastic_resume = args.resume
        if args.resume == 'auto':
            elastic_resume = (resolve_auto_resume(probe_dir) or '') if probe_dir else ''
        plan = plan_elastic_resume(
            devices=jax.device_count(),
            batch_size=args.batch_size, grad_accum=args.grad_accum_steps,
            fsdp=args.fsdp or None, tp=args.tp or None, resume=elastic_resume,
            model=args.model, model_kwargs=_solver_model_kwargs(args))
        args.fsdp, args.tp = plan.fsdp or 0, plan.tp or 0
        args.batch_size, args.grad_accum_steps = plan.batch_size, plan.grad_accum
        for note in plan.notes:
            _logger.info(f'[elastic] {note}')
        _logger.info(
            f'[elastic] live topology: {plan.devices} devices, fsdp={plan.fsdp}, '
            f'tp={plan.tp}; global batch {plan.global_batch} = '
            f'{plan.batch_size} x {plan.grad_accum}'
            + (f' (held constant from {os.path.basename(plan.source)})' if plan.source else ''))

    if args.autotune:
        # rank every legal config for the live topology at the (possibly
        # elastic-recovered) global batch, then apply the winner's flags —
        # all before the mesh exists, so the run IS the winning config
        from timm_tpu.autotune import apply_to_args, autotune, format_table
        result = autotune(
            args.model, _solver_model_kwargs(args),
            global_batch=args.batch_size * args.grad_accum_steps,
            probe_top_k=args.autotune_probe_top_k,
            log=lambda m: _logger.info(f'[autotune] {m}'))
        for line in format_table(result).splitlines():
            _logger.info(f'[autotune] {line}')
        for note in apply_to_args(args, result):
            _logger.info(f'[autotune] applied {note}')

    mesh = create_mesh(fsdp=args.fsdp if args.fsdp else None,
                       tp=args.tp if args.tp else None)
    set_global_mesh(mesh)
    n_devices = mesh.size
    _logger.info(f'Training on mesh {mesh} ({n_devices} devices, {world_size} processes)')

    dtype = jnp.bfloat16 if args.amp else None
    model_kwargs = dict(args.model_kwargs)
    if args.drop:
        model_kwargs['drop_rate'] = args.drop
    if args.drop_path is not None:
        model_kwargs['drop_path_rate'] = args.drop_path
    factory_kwargs = dict(
        pretrained=args.pretrained,
        num_classes=args.num_classes,
        in_chans=args.in_chans,
        checkpoint_path=args.initial_checkpoint,
        dtype=dtype,
        seed=args.seed,
    )
    # pass img_size only to models whose constructor takes it; fixed-field
    # conv nets get resized inputs via resolve_data_config instead. The retry
    # is limited to the exact img_size TypeError so real errors still surface.
    def _build_model():
        if args.img_size is not None:
            try:
                return create_model(args.model, img_size=args.img_size, **factory_kwargs, **model_kwargs)
            except TypeError as e:
                if 'img_size' not in str(e):
                    raise
        return create_model(args.model, **factory_kwargs, **model_kwargs)

    with tracing.span('setup.model_build'):
        if 'fsdp' in mesh.axis_names or 'model' in mesh.axis_names:
            # abstract init: nnx.eval_shape resolves the partition rules against
            # the abstract param shapes and a jitted constructor materializes each
            # shard on its owning devices — a replicated full-model copy never
            # exists (falls back to eager build + reshard for non-traceable
            # constructors, e.g. pretrained-weight loading)
            from timm_tpu.parallel import create_sharded_model
            model = create_sharded_model(_build_model, mesh)
        else:
            model = _build_model()
    if args.num_classes is None:
        args.num_classes = model.num_classes
    # the model's kind picks the task and the feed, in one place (`TASK_KINDS`)
    task_kind = getattr(model, 'task_kind', None)
    if task_kind not in TASK_KINDS:
        raise ValueError(f'{args.model} is of kind {task_kind!r}; train.py knows {sorted(map(str, TASK_KINDS))}')
    kind_task, kind_feed = TASK_KINDS[task_kind]
    token_model = kind_feed == 'tokens'
    if token_model != (args.dataset == 'tokens'):
        raise ValueError(f'--dataset tokens and a language model ({", ".join(TOKEN_KINDS)}) go together: '
                         f'{args.model} is of kind {task_kind!r}, --dataset is {args.dataset!r}')
    if args.grad_checkpointing:
        model.set_grad_checkpointing(True)
    if args.block_scan:
        if hasattr(model, 'set_block_scan'):
            model.set_block_scan(True)
        else:
            _logger.warning(f'--block-scan: {args.model} has no scannable block stack; ignored')

    # AugMix aug-splits (reference train.py:886-913): wrap BNs with per-split
    # statistics before the optimizer captures the param tree
    num_aug_splits = 0
    if args.aug_splits > 0:
        assert args.aug_splits > 1, 'a split of 1 makes no sense'
        num_aug_splits = args.aug_splits
    if args.split_bn:
        assert num_aug_splits > 1
        from timm_tpu.layers import convert_splitbn_model
        model = convert_splitbn_model(model, max(num_aug_splits, 2))

    from timm_tpu.data import resolve_data_config
    data_config = resolve_data_config(vars(args), model=model, verbose=rank == 0)
    img_size = data_config['input_size'][-1]

    # LR auto-scale from global batch (ref train.py:837-849)
    global_batch_size = args.batch_size * args.grad_accum_steps
    if args.lr is None:
        on = args.opt.lower()
        scale = 'sqrt' if any(o in on for o in ('ada', 'lamb', 'lion')) else 'linear'
        if args.lr_base_scale:
            scale = args.lr_base_scale
        batch_ratio = global_batch_size / args.lr_base_size
        if scale == 'sqrt':
            batch_ratio = batch_ratio ** 0.5
        args.lr = args.lr_base * batch_ratio
        _logger.info(f'LR ({args.lr}) from base ({args.lr_base}) * {scale} batch ratio')

    # distillation teacher: built (and, for feature distill, the student's
    # projection attached) BEFORE the optimizer captures the param tree
    distill = _parse_distill(args.distill) if args.distill else None
    teacher = None
    if distill is not None:
        if args.naflex_loader:
            raise ValueError('--distill does not compose with --naflex-loader '
                             '(the teacher forward expects dense NHWC batches)')
        from timm_tpu.models import load_checkpoint
        from timm_tpu.task import FeatureDistillationTask, LogitDistillationTask
        teacher_kwargs = dict(num_classes=args.num_classes, in_chans=args.in_chans, dtype=dtype)
        try:
            teacher = create_model(distill['teacher'], img_size=img_size, **teacher_kwargs)
        except TypeError as e:
            if 'img_size' not in str(e):
                raise
            teacher = create_model(distill['teacher'], **teacher_kwargs)
        if distill['checkpoint']:
            load_checkpoint(teacher, distill['checkpoint'])
        teacher.eval()
        if distill['kind'] == 'feature':
            FeatureDistillationTask.prepare_model(model, teacher)
        _logger.info(
            f"Distilling from teacher {distill['teacher']} "
            f"({distill['kind']}, alpha={distill['alpha']}, "
            + (f"T={distill['temperature']}" if distill['kind'] == 'logit'
               else f"feat_loss={distill['feat_loss']}") + ')')

    with tracing.span('setup.task_build'):
        optimizer = create_optimizer_v2(model, **optimizer_kwargs(args))
        norm_mean = data_config['mean']
        norm_std = data_config['std']
        if args.naflex_loader:
            from timm_tpu.task import NaFlexClassificationTask
            task_cls = NaFlexClassificationTask
            # NaFlex batches are normalized host-side by the loader
            norm_mean = norm_std = None
        else:
            from timm_tpu import task as tasks
            task_cls = getattr(tasks, kind_task)
        if distill is not None:
            task_cls = (LogitDistillationTask if distill['kind'] == 'logit'
                        else FeatureDistillationTask)
        if args.device_augment:
            if args.grad_accum_steps != 1:
                raise ValueError(
                    '--device-augment yields device-resident batches; the host-side '
                    'micro-batch concatenation of --grad-accum-steps > 1 would bounce '
                    'them back to host. Use --grad-accum-steps 1')
            if num_aug_splits > 1:
                raise ValueError('--device-augment does not compose with --aug-splits '
                                 '(split-batch augmentation collates on host)')
            if not args.naflex_loader and (args.synthetic_data or not args.data_dir):
                raise ValueError('--device-augment needs a real dataset pipeline; '
                                 'pass --data-dir (synthetic batches are already device floats)')
            # the on-device augment stage normalizes; the task must not re-normalize
            norm_mean = norm_std = None
        task_kwargs = {}
        if args.naflex_loader and (args.mixup > 0 or args.cutmix > 0):
            # smoothing folds into the soft mixed targets (reference mixup_target)
            task_kwargs['mixup_label_smoothing'] = args.smoothing
        if distill is not None:
            task_kwargs['teacher'] = teacher
            task_kwargs['distill_alpha'] = distill['alpha']
            if distill['kind'] == 'logit':
                task_kwargs['distill_temperature'] = distill['temperature']
            else:
                task_kwargs['feat_loss'] = distill['feat_loss']
        task = task_cls(
            model,
            optimizer=optimizer,
            mesh=mesh,
            grad_accum_steps=args.grad_accum_steps,
            clip_grad=args.clip_grad,
            clip_mode=args.clip_mode,
            mean=norm_mean,
            std=norm_std,
            nonfinite_guard=False if args.no_nonfinite_guard else None,
            nonfinite_tolerance=args.nonfinite_tolerance,
            **task_kwargs,
        )

        if 'fsdp' in mesh.axis_names or 'model' in mesh.axis_names:
            from flax import nnx
            from timm_tpu.parallel import activation_bytes_per_device, param_bytes_per_device
            rep_b, shard_b = param_bytes_per_device(nnx.state(model, nnx.Param), mesh)
            axes_str = ' x '.join(f'{a}={mesh.shape[a]}' for a in mesh.axis_names)
            _logger.info(
                f'Sharded mesh ({axes_str}): params per device '
                f'{shard_b / 1e6:.1f} MB (vs {rep_b / 1e6:.1f} MB replicated); optimizer '
                f'm/v shard identically (parallel/sharding.py rules)')
            width = getattr(model, 'embed_dim', None)
            depth = len(getattr(model, 'blocks', None) or ())
            seq_len = getattr(getattr(model, 'patch_embed', None), 'num_patches', None)
            if width and depth and seq_len:
                act_u, act_c = activation_bytes_per_device(
                    mesh, batch_size=args.batch_size, seq_len=seq_len, width=width, depth=depth)
                _logger.info(
                    f'Estimated block activations per device: {act_c / 1e6:.1f} MB with '
                    f'activation sharding constraints (vs {act_u / 1e6:.1f} MB without)')

        # loss selection (ref train.py:886-913)
        if args.jsd_loss:
            assert num_aug_splits > 1, '--jsd-loss requires --aug-splits > 1'
            from timm_tpu.loss import JsdCrossEntropy
            train_loss = JsdCrossEntropy(num_splits=num_aug_splits, smoothing=args.smoothing)
        elif args.mixup > 0 or args.cutmix > 0:
            train_loss = BinaryCrossEntropy(
                smoothing=0.0, target_threshold=args.bce_target_thresh, sum_classes=args.bce_sum,
            ) if args.bce_loss else SoftTargetCrossEntropy()
        elif args.smoothing:
            train_loss = BinaryCrossEntropy(
                smoothing=args.smoothing, target_threshold=args.bce_target_thresh, sum_classes=args.bce_sum,
            ) if args.bce_loss else LabelSmoothingCrossEntropy(smoothing=args.smoothing)
        else:
            train_loss = LabelSmoothingCrossEntropy(0.0)
        task.train_loss_fn = train_loss

        if args.model_ema:
            task.setup_ema(decay=args.model_ema_decay, warmup=args.model_ema_warmup)

    # data
    with tracing.span('setup.data_build'):
        if args.naflex_loader:
            if not args.data_dir:
                raise ValueError('--naflex-loader requires --data-dir')
            from timm_tpu.data import create_dataset
            from timm_tpu.data.naflex_loader import create_naflex_loader
            patch_size = getattr(model.embeds, 'patch_size', 16) if hasattr(model, 'embeds') else 16
            dataset_train = create_dataset(
                args.dataset, root=args.data_dir, split=args.train_split, is_training=True,
                class_map=args.class_map)
            dataset_eval = create_dataset(
                args.dataset, root=args.data_dir, split=args.val_split, class_map=args.class_map)
            loader_train = create_naflex_loader(
                dataset_train, patch_size=patch_size,
                patch_size_choices=tuple(args.naflex_patch_sizes) if args.naflex_patch_sizes else None,
                train_seq_lens=tuple(args.naflex_train_seq_lens),
                max_seq_len=args.naflex_max_seq_len,
                batch_size=args.batch_size, is_training=True,
                mean=data_config['mean'], std=data_config['std'],
                interpolation=data_config['interpolation'], hflip=args.hflip,
                mixup_alpha=args.mixup, cutmix_alpha=args.cutmix,
                mixup_prob=args.mixup_prob, mixup_switch_prob=args.mixup_switch_prob,
                re_prob=args.reprob, re_mode='pixel' if args.remode == 'pixel' else 'const',
                seed=args.seed, grad_accum_steps=args.grad_accum_steps,
                device_augment=args.device_augment,
                bucket_mode=args.naflex_bucket_mode,
                device_prefetch=args.device_prefetch if args.device_augment else 0)
            loader_eval = create_naflex_loader(
                dataset_eval, patch_size=patch_size,
                max_seq_len=args.naflex_max_seq_len,
                batch_size=args.validation_batch_size or args.batch_size,
                mean=data_config['mean'], std=data_config['std'],
                interpolation=data_config['interpolation'], seed=args.seed)
            mixup_fn = None
        elif token_model:
            from timm_tpu.data import create_dataset
            from timm_tpu.data.loader import ThreadedLoader
            if not args.data_dir:
                raise ValueError('--dataset tokens needs --data-dir (train.bin / validation.bin of raw int32 ids)')
            splits = {True: args.train_split, False: args.val_split}
            loader_train, loader_eval = (
                ThreadedLoader(
                    create_dataset('tokens', root=args.data_dir, split=splits[training], is_training=training,
                                   num_classes=args.num_classes, seq_len=args.seq_len),
                    batch_size=args.batch_size if training else args.validation_batch_size or args.batch_size,
                    is_training=training, num_workers=args.workers, seed=args.seed,
                    process_index=rank, process_count=world_size)
                for training in (True, False))
            mixup_fn = None
        elif args.synthetic_data or not args.data_dir:
            _logger.info('Using synthetic data')
            loader_train = SyntheticLoader(args.synthetic_len, args.batch_size, img_size,
                                           args.num_classes, args.seed,
                                           process_index=rank, process_count=world_size)
            loader_eval = SyntheticLoader(max(args.synthetic_len // 4, args.batch_size),
                                          args.validation_batch_size or args.batch_size,
                                          img_size, args.num_classes, args.seed + 1,
                                          process_index=rank, process_count=world_size)
            mixup_fn = 'auto'
        else:
            from timm_tpu.data import create_dataset, create_loader
            dataset_train = create_dataset(
                args.dataset, root=args.data_dir, split=args.train_split, is_training=True,
                class_map=args.class_map, num_classes=args.num_classes)
            dataset_eval = create_dataset(
                args.dataset, root=args.data_dir, split=args.val_split, is_training=False,
                class_map=args.class_map, num_classes=args.num_classes)
            if num_aug_splits > 1:
                if not hasattr(dataset_train, '__getitem__'):
                    raise ValueError(
                        '--aug-splits requires a map-style dataset (folder/tar/hfds); '
                        'streaming schemes (wds/tfds/hfids) are not supported')
                from timm_tpu.data.dataset import AugMixDataset
                dataset_train = AugMixDataset(dataset_train, num_splits=num_aug_splits)
            train_mixup = None
            if args.device_augment and (args.mixup > 0 or args.cutmix > 0):
                # parameter sampler only — the pixel/target math runs in the
                # loader's jitted on-device program (data/device_augment.py)
                from timm_tpu.data.mixup import Mixup
                train_mixup = Mixup(
                    mixup_alpha=args.mixup, cutmix_alpha=args.cutmix, cutmix_minmax=args.cutmix_minmax,
                    prob=args.mixup_prob, switch_prob=args.mixup_switch_prob, mode=args.mixup_mode,
                    label_smoothing=args.smoothing, num_classes=args.num_classes, seed=args.seed)
            loader_train = create_loader(
                dataset_train,
                input_size=data_config['input_size'],
                batch_size=args.batch_size,
                is_training=True,
                no_aug=args.no_aug,
                scale=args.scale,
                ratio=args.ratio,
                hflip=args.hflip,
                vflip=args.vflip,
                color_jitter=args.color_jitter,
                auto_augment=args.aa,
                re_prob=args.reprob,
                re_mode=args.remode,
                re_count=args.recount,
                num_aug_splits=num_aug_splits,
                interpolation=args.train_interpolation,
                mean=data_config['mean'],
                std=data_config['std'],
                num_workers=args.workers,
                seed=args.seed,
                device_augment=args.device_augment,
                mixup=train_mixup,
                device_prefetch=args.device_prefetch if args.device_augment else 0,
            )
            # the decode processes import and unpickle while the rest of set-up runs; the
            # evaluation loader starts its own at its first use, if it has one
            if hasattr(loader_train, 'start'):
                loader_train.start()
            loader_eval = create_loader(
                dataset_eval,
                input_size=data_config['input_size'],
                batch_size=args.validation_batch_size or args.batch_size,
                is_training=False,
                interpolation=data_config['interpolation'],
                mean=data_config['mean'],
                std=data_config['std'],
                num_workers=args.workers,
                crop_pct=data_config['crop_pct'],
            )
            # device_augment folds mixup into the loader's on-device program
            mixup_fn = None if args.device_augment else 'auto'

        # mixup applies to any (input, target)-tuple loader; naflex handles its own
        if mixup_fn == 'auto':
            from timm_tpu.data.mixup import Mixup
            mixup_fn = None
            if args.mixup > 0 or args.cutmix > 0:
                mixup_fn = Mixup(
                    mixup_alpha=args.mixup, cutmix_alpha=args.cutmix, cutmix_minmax=args.cutmix_minmax,
                    prob=args.mixup_prob, switch_prob=args.mixup_switch_prob, mode=args.mixup_mode,
                    label_smoothing=args.smoothing, num_classes=args.num_classes)

        if args.device_prefetch:
            from timm_tpu.data.loader import DevicePrefetcher
            loader_eval = DevicePrefetcher(loader_eval, size=args.device_prefetch)
            if args.device_augment:
                # create_loader / create_naflex_loader already prefetch inside
                # the device-augment stack; batches here are device-resident
                pass
            elif mixup_fn is None and args.grad_accum_steps == 1:
                loader_train = DevicePrefetcher(loader_train, size=args.device_prefetch)
            else:
                # mixup / grad-accum concatenation still mutate batches on host;
                # prefetching to device first would bounce them straight back
                _logger.info('--device-prefetch: train loader stays on host '
                             '(mixup or --grad-accum-steps > 1 active); eval loader prefetches')

    # scheduler
    try:
        steps_per_epoch = len(loader_train)
    except TypeError:
        # streaming dataset with unknown length: --epoch-size defines the epoch
        if not args.epoch_size:
            raise ValueError(
                'streaming dataset has no known length; pass --epoch-size N '
                '(samples per epoch) or provide an _info.json shard sidecar')
        steps_per_epoch = max(args.epoch_size // args.batch_size, 1)
    if args.naflex_loader:
        # each NaFlex loader batch is one update (accumulation happens INSIDE
        # task.train_step over microbatches of the accum-scaled batch)
        updates_per_epoch = steps_per_epoch
    else:
        updates_per_epoch = (steps_per_epoch + args.grad_accum_steps - 1) // args.grad_accum_steps
    lr_scheduler, num_epochs = create_scheduler_v2(
        base_lr=args.lr,
        **{k: v for k, v in scheduler_kwargs(args).items() if k != 'num_epochs'},
        num_epochs=args.epochs,
        updates_per_epoch=updates_per_epoch,
    )
    start_epoch = 0
    if args.start_epoch is not None:
        start_epoch = args.start_epoch

    # output / saver — created BEFORE resume so `--resume auto` can scan the
    # experiment dir (pass --experiment for a stable dir across restarts);
    # CheckpointSaver's constructor also sweeps orphaned tmp / corrupt
    # recovery files left by a crash
    saver = None
    output_dir = None
    exp_name = args.experiment or '-'.join([
        datetime.now().strftime('%Y%m%d-%H%M%S'), args.model, str(img_size)])
    async_writer = None
    if rank == 0:
        output_dir = get_outdir(args.output if args.output else './output/train', exp_name)
    elif args.experiment:
        # non-primary hosts resolve the same (shared-FS) dir for auto-resume
        # and — multi-process — for their own checkpoint shard files
        output_dir = os.path.join(args.output if args.output else './output/train', exp_name)
        os.makedirs(output_dir, exist_ok=True)
    if output_dir is not None and (rank == 0 or world_size > 1):
        if os.environ.get('TIMM_TPU_ASYNC_CKPT', '1') != '0':
            # async checkpointing (default on): the step loop only snapshots
            # state to host; fsync/os.replace run on this writer thread.
            # TIMM_TPU_ASYNC_CKPT=0 restores fully synchronous writes.
            # Multi-process keeps one writer thread PER PROCESS: each host
            # writes only its own shard file.
            async_writer = AsyncCheckpointWriter()
        saver = CheckpointSaver(
            task, args=args, checkpoint_dir=output_dir, recovery_dir=output_dir,
            decreasing=args.eval_metric == 'loss', max_history=args.checkpoint_hist,
            async_writer=async_writer,
            process_index=rank, process_count=world_size)
    if rank == 0 and output_dir is not None:
        with open(os.path.join(output_dir, 'args.yaml'), 'w') as f:
            f.write(args_text)

    # resume: integrity-verified load with fallback to the newest valid
    # checkpoint; 'auto' resolves recovery/last/checkpoint-* newest-first
    start_batch_idx = 0
    resume_num_updates = None
    resume_path = ''
    if args.resume == 'auto':
        resume_path = resolve_auto_resume(output_dir) if output_dir else None
        if not resume_path:
            _logger.info(f'auto-resume: no valid checkpoint under {output_dir}; starting fresh')
    elif args.resume:
        resume_path = args.resume
    if resume_path:
        state, _ck_meta, used_path = load_with_fallback(
            resume_path, search_dir=output_dir or os.path.dirname(os.path.abspath(resume_path)))
        # one-line diff of state keys instead of a strict=True stack trace
        template = set(task.get_checkpoint_state())
        loaded = {k for k in state if not k.startswith('_resume.') and k not in ('epoch', 'metric')}
        missing, unexpected = sorted(template - loaded), sorted(loaded - template)
        if missing or unexpected:
            _logger.warning(
                f'Resume state diff: {len(missing)} missing '
                f'{missing[:5] + (["..."] if len(missing) > 5 else [])}, '
                f'{len(unexpected)} unexpected '
                f'{unexpected[:5] + (["..."] if len(unexpected) > 5 else [])}')
        task.load_checkpoint_state(state, strict=False, load_opt=not args.no_resume_opt)
        restore_host_rng(state)
        ck_epoch = int(state['epoch']) if 'epoch' in state else 0
        if state.get('_resume.mid_epoch') is not None and int(state['_resume.mid_epoch']):
            # step-granular recovery: re-enter the SAME epoch, skip the
            # already-consumed loader batches, continue the update counter
            start_epoch = ck_epoch
            start_batch_idx = int(state['_resume.batches_consumed'])
            if '_resume.batch_size' in state:
                old_bs = int(state['_resume.batch_size'])
                if old_bs != args.batch_size:
                    start_batch_idx, exact = convert_loader_position(
                        start_batch_idx, old_bs, args.batch_size)
                    _logger.warning(
                        f'Loader batch size changed {old_bs} -> {args.batch_size} on '
                        f'resume: position converted to {start_batch_idx} batches'
                        + ('' if exact else ' (inexact: partial batch re-seen)')
                        + '; data order is only bit-identical when the loader '
                          'batch size is unchanged')
            resume_num_updates = int(state['_resume.num_updates'])
            _logger.info(
                f'Resumed mid-epoch from {used_path}: epoch {start_epoch}, '
                f'batch {start_batch_idx}, update {resume_num_updates}')
        else:
            if args.start_epoch is None:
                start_epoch = ck_epoch + 1
            _logger.info(f'Resumed from {used_path} at epoch {start_epoch}')

    # prime the scheduler so epoch 0 (or the resume epoch) starts at warmup LR
    if lr_scheduler is not None:
        if args.sched_on_updates:
            lr_scheduler.step_update(resume_num_updates if resume_num_updates is not None
                                     else start_epoch * updates_per_epoch)
        else:
            lr_scheduler.step(start_epoch)
            if resume_num_updates is not None:
                lr_scheduler.step_update(resume_num_updates)

    # preemption-aware shutdown: SIGTERM/SIGINT set a flag the train loop
    # polls; on preemption a step-granular recovery checkpoint is written and
    # the process exits 0 (resume with `--resume auto`)
    shutdown = GracefulShutdown().install()
    rollback_budget = [int(os.environ.get('TIMM_TPU_ROLLBACK_BUDGET', '1'))
                       if args.nonfinite_rollback else 0]

    best_metric = None
    best_epoch = None
    eval_metrics = {}
    try:
        for epoch in range(start_epoch, num_epochs):
            if shutdown.requested:
                # preempted at an epoch boundary: last.npz already covers resume
                _logger.warning(f'Shutdown requested; stopping before epoch {epoch} '
                                f'(resume with --resume auto)')
                raise SystemExit(0)
            if hasattr(loader_train, 'set_epoch'):
                loader_train.set_epoch(epoch)  # fresh shuffle/schedule (ref train.py:478)
            if args.mixup_off_epoch and epoch >= args.mixup_off_epoch:
                if mixup_fn is not None:
                    mixup_fn.mixup_enabled = False  # ref train.py disable-mixup schedule
                elif getattr(loader_train, 'mixup', None) is not None:
                    # device-augment stage: same schedule; the sampler emits
                    # identity params (lam=1) so the jitted program is unchanged
                    loader_train.mixup.mixup_enabled = False
            try:
                train_metrics = train_one_epoch(
                    epoch, task, loader_train, args, lr_scheduler, mesh, shard_batch,
                    updates_per_epoch, saver=saver, mixup_fn=mixup_fn, shutdown=shutdown,
                    skip_batches=start_batch_idx if epoch == start_epoch else 0,
                    start_updates=resume_num_updates if epoch == start_epoch else None,
                    rollback_budget=rollback_budget)
            except TrainingPreempted as e:
                _logger.warning(f'Preempted during epoch {epoch}; recovery checkpoint: '
                                f'{e.recovery_path or "(non-primary host)"}. Exiting 0 for reschedule.')
                raise SystemExit(0)
            except NonFiniteError as e:
                _logger.error(f'Aborting training: {e}')
                raise SystemExit(3)

            eval_metrics = validate(task, loader_eval, args, mesh, shard_batch)
            if task.ema_params is not None:
                ema_metrics = validate(task, loader_eval, args, mesh, shard_batch, use_ema=True)
                eval_metrics.update({f'{k}_ema': v for k, v in ema_metrics.items()})

            if output_dir is not None and is_primary(args):
                update_summary(
                    epoch, train_metrics, eval_metrics,
                    filename=os.path.join(output_dir, 'summary.csv'),
                    lr=train_metrics.get('lr'),
                    write_header=epoch == start_epoch, log_wandb=args.log_wandb)
            if saver is not None:
                best_metric, best_epoch = saver.save_checkpoint(epoch, metric=eval_metrics.get(args.eval_metric))
            if lr_scheduler is not None:
                lr_scheduler.step(epoch + 1, eval_metrics.get(args.eval_metric))
    finally:
        # drain the async writer on EVERY exit — including the SystemExit(0)
        # a SIGTERM/TrainingPreempted turns into — so the recovery checkpoint
        # is durable before the scheduler restarts us. A pending write failure
        # raises here: an undrained writer must fail as loudly as a sync one.
        try:
            if async_writer is not None:
                async_writer.close()
        finally:
            # on every way out, an exception from a step included: no decode process outlives the run
            for loader in (loader_train, loader_eval):
                getattr(loader, 'close', lambda: None)()
            shutdown.uninstall()
            _thaw()

    if best_metric is not None:
        _logger.info(f'*** Best metric: {best_metric} (epoch {best_epoch})')
        if is_primary(args):
            print(json.dumps({'result': {args.eval_metric: best_metric, 'epoch': best_epoch}}))
    return eval_metrics


def _recovery_extras(batches_consumed, num_updates, args=None):
    """Step-granular resume state stored alongside the task state in a
    recovery checkpoint: loader position, update counter, host RNG streams —
    plus the batch geometry an `--elastic` restart needs to hold the global
    batch constant on a different topology."""
    from timm_tpu.resilience import capture_host_rng
    extras = {
        '_resume.mid_epoch': np.asarray(1),
        '_resume.batches_consumed': np.asarray(batches_consumed),
        '_resume.num_updates': np.asarray(num_updates),
    }
    if args is not None:
        extras['_resume.batch_size'] = np.asarray(args.batch_size)
        extras['_resume.global_batch'] = np.asarray(args.batch_size * args.grad_accum_steps)
        extras['_resume.device_count'] = np.asarray(jax.device_count())
        extras['_resume.process_count'] = np.asarray(jax.process_count())
    extras.update(capture_host_rng())
    return extras


_frozen = False  # gc.freeze() is process-wide and so is this: whether the run in progress has frozen


def _freeze_once():
    """Once a run, after its first step: what set-up built (the model, the compiled
    step, JAX's caches: hundreds of thousands of containers) lives as long as the
    run. Out of the cyclic collector's reach until `main` returns, or every few
    seconds a full collection walks all of it, finds nothing, and holds the
    interpreter lock for over 100 ms (PERF.md section 6, PR 25)."""
    global _frozen
    if not _frozen:
        gc.collect()
        gc.freeze()
        _frozen = True


def _thaw():
    """What `_freeze_once` froze is collectable again: a task sits in a cycle with
    its jitted step, and frozen it would keep its device state for the process's life."""
    global _frozen
    if _frozen:
        gc.unfreeze()
        _frozen = False


def _or_rollback(task, call, saver, rollback_budget):
    """`call()` (a `task.train_step` or a `task.drain`: what reads a step's
    non-finite counters) with optional rollback-to-last-checkpoint when the
    non-finite tolerance trips in it. Returns its result, or None after a
    rollback: the caller of a step skips the batch and continues. The counters
    read are those of the step BEFORE the one a `train_step` enqueues: the
    rollback discards that one too."""
    from timm_tpu.resilience import NonFiniteError, load_with_fallback, resolve_auto_resume
    try:
        return call()
    except NonFiniteError as e:
        if not rollback_budget or rollback_budget[0] <= 0 or saver is None:
            raise
        if saver.async_writer is not None:
            saver.async_writer.drain()  # the newest file may still be with the writer thread
        rb = resolve_auto_resume(saver.checkpoint_dir)
        if rb is None:
            raise
        state, _meta, used = load_with_fallback(rb, search_dir=saver.checkpoint_dir)
        task.load_checkpoint_state(state, strict=False)
        task.reset_nonfinite()
        rollback_budget[0] -= 1
        _logger.warning(
            f'Non-finite tolerance hit at update {e.step}: rolled back to {used} '
            f'({rollback_budget[0]} rollback(s) left); continuing')
        return None


def _traced_batches(loader, num_updates):
    """(batch index, batch) from `loader`, under one `train.step` root span per
    update: the root opens before the fetch and stays open, over as many
    fetches as the update takes (accumulation, resume skips, a rolled-back
    step), until the loop body comes back with `num_updates()` advanced.
    Closing the generator closes the open root."""
    from timm_tpu.utils import tracing
    batches = enumerate(loader)
    while True:
        step = num_updates()
        with tracing.span('train.step', step=step):
            while num_updates() == step:
                with tracing.span('train.loader_next'):
                    item = next(batches, None)
                if item is None:
                    return
                yield item


def _batch_to_device(arrays, mesh, shard_batch):
    from timm_tpu.utils import tracing
    with tracing.span('train.batch_to_device'):
        return shard_batch({k: jnp.asarray(v) for k, v in arrays.items()}, mesh)


def _host_line(since_ns, counters_before, metrics=None, expert_layers=0):
    """The log line's host breakdown since the previous line (README
    "Reading the host breakdown"): mean wall ms per update of each part of the
    loop from the program's spans, and what the loader's threads did; before
    it, for a model with expert layers, how many of the step's `expert_layers`
    took the worst-case dispatch buffer; after `loop`, on a line since whose predecessor `Attention` calls were
    traced, how many took the kernel pair (`fused`) and how many `_sdpa` (`plain`); at the end, once the step program
    was compiled ahead of time and kept, what its text says: `kda scans` and `route gathers`. -> (text, the counters now)."""
    from timm_tpu.utils import tracing
    snap = tracing.snapshot()
    rows = tracing.summary(since_ns, spans=snap['spans'])
    steps = max(rows.get('task.train_step', {}).get('n', 0), 1)
    ms = lambda *names: sum(rows[k]['wall_ms_sum'] for k in names if k in rows) / steps  # noqa: E731
    did = {k: v - counters_before.get(k, 0) for k, v in snap['counters'].items()}
    depths = [v for t, v in snap['gauges'].get('loader.batch_q_depth', ()) if t >= since_ns]
    procs = snap['gauges'].get('loader.decode_procs')
    polls = did.get('task.sentinel_polls', 0)
    text = (f"host ms/step: next {ms('train.loader_next'):.1f} split {ms('task.state_split'):.1f} "
            f"put {ms('task.scalars_put', 'train.batch_to_device'):.1f} call {ms('task.step_call'):.1f} "
            f"update {ms('task.state_update'):.1f} poll {ms('task.sentinel_poll'):.1f} "
            f"loop {ms('train.bookkeeping', 'train.log_sync'):.1f}")
    if did.get('attention.fused_calls') or did.get('attention.plain_calls'):
        # `Attention` calls traced since the previous line (a run's first line: the step's) and the core each took
        text += f" attn fused {did.get('attention.fused_calls', 0)} plain {did.get('attention.plain_calls', 0)}"
    if did.get('loader.samples') and did.get('loader.batches'):
        text += (f" | loader q {sum(depths) / max(len(depths), 1):.1f} "
                 f"decode {did['loader.decode_busy_ns'] / did['loader.samples'] / 1e6:.1f} ms/img "
                 f"{did['loader.decode_busy_ns'] / did['loader.batches'] / 1e6:.0f} ms/batch "
                 f"polls {polls} ahead {did.get('task.polls_host_ahead', 0)}/{polls} "
                 f"binds {did.get('task.state_binds', 0)}")
    if procs:   # an image run: decode processes alive at the newest fetch, and those that ended unasked
        text += f" procs {procs[-1][1]} exits {did.get('loader.worker_exits', 0)}"
    if metrics and 'moe.fallback_layers' in metrics:
        text = f"fallback {int(metrics['moe.fallback_layers'])} of {expert_layers} layers " + text
    if 'ffn.products' in snap['gauges']:        # as the two below: read from the kept step program's text
        text += f" ffn products {snap['gauges']['ffn.products'][-1][1]}"
    if 'kda.core_scans' in snap['gauges']:      # as the route's gathers below: read from the kept step program's text
        text += f" kda scans {snap['gauges']['kda.core_scans'][-1][1]}"
    if 'moe.route_gathers' in snap['gauges']:   # set where the step program was compiled ahead of time and kept
        text += (f" route gathers {snap['gauges']['moe.route_gathers'][-1][1]} "
                 f"fast {snap['gauges']['moe.route_gathers_fast'][-1][1]}")
    return text, snap['counters']


def _setup_line():
    """Where set-up went, once, after the process's first step."""
    from timm_tpu.utils import tracing
    rows = tracing.summary()
    s = lambda name: rows.get(name, {}).get('wall_ms_sum', 0.0) / 1e3  # noqa: E731
    built = rows.get('xla.backend_compile', {})
    return (f"setup s: model {s('setup.model_build'):.1f} task {s('setup.task_build'):.1f} "
            f"data {s('setup.data_build'):.1f} first step {s('task.step_call'):.1f} "
            f"compiles {built.get('n', 0)} ({s('xla.backend_compile'):.1f} s)")


def train_one_epoch(epoch, task, loader, args, lr_scheduler, mesh, shard_batch,
                    updates_per_epoch, saver=None, mixup_fn=None, shutdown=None,
                    skip_batches=0, start_updates=None, rollback_budget=None):
    from flax import nnx

    from timm_tpu.layers import SparseMoe
    from timm_tpu.resilience import TrainingPreempted, get_fault_injector
    from timm_tpu.utils import AverageMeter, tracing
    loss_m = AverageMeter()
    accum = args.grad_accum_steps
    # expert-layer calls an update makes: the log line's `fallback .. of .. layers`
    expert_layers = accum * sum(isinstance(m, SparseMoe) for _, m in nnx.iter_modules(task.model))
    num_updates = start_updates if start_updates is not None else epoch * updates_per_epoch
    lr = lr_scheduler.get_last_lr()[0] if lr_scheduler else args.lr
    injector = get_fault_injector()

    def drain():
        """The newest step's non-finite counters, read before anything is saved,
        evaluated or killed: `train_step` itself reads one step behind. Aborts
        or rolls back as a step does."""
        _or_rollback(task, task.drain, saver, rollback_budget)

    def poll_faults_and_shutdown(batch_idx, update_idx):
        """After each committed update: deliver injected SIGKILL/SIGTERM, then
        write a step-granular recovery checkpoint and stop if shutdown was
        requested."""
        if injector is not None and injector.kill_host_at(num_updates - 1, jax.process_index()):
            # host-loss drill: die NOW, before any consensus/recovery save —
            # the victim must never publish its stop vote, so the survivors'
            # next named consensus times out on it and resolves to stop.
            # Drain the dispatched step first (its collective sends must land
            # so survivors can materialize the post-step state on their own).
            drain()
            jax.block_until_ready((metrics, task.opt_state))
            _logger.warning(f'[fault-inject] kill_host at update {num_updates - 1}: SIGKILL')
            os.kill(os.getpid(), __import__('signal').SIGKILL)
        if injector is not None and injector.sigterm_at(num_updates - 1):
            _logger.warning(f'[fault-inject] SIGTERM at update {num_updates - 1}')
            os.kill(os.getpid(), __import__('signal').SIGTERM)
        if injector is not None and injector.resize_at(num_updates - 1):
            # in-process, a resize IS a preemption: SIGTERM now; the restart
            # harness (tests/fsdp_drill.py) relaunches with the new topology
            _logger.warning(f'[fault-inject] resize to {injector.resize_devices} '
                            f'devices at update {num_updates - 1}: delivering SIGTERM')
            os.kill(os.getpid(), __import__('signal').SIGTERM)
        if shutdown is not None and shutdown.should_stop(update_idx):
            path = ''
            if saver is not None:
                drain()
                path = saver.save_recovery(
                    epoch, update_idx,
                    extra_state=_recovery_extras(batch_idx + 1, num_updates, args))
            raise TrainingPreempted(path)

    metrics = {}
    micro_inputs, micro_targets = [], []
    update_idx = skip_batches // accum  # display/recovery cadence continuity on resume
    samples_since_log = 0
    log_t0 = time.time()
    log_since_ns, ring = tracing.now_ns(), tracing.snapshot()
    log_counters = ring['counters']
    log_setup = not any(s.name == 'task.step_call' for s in ring['spans'])  # the process's first step is still to come
    del ring
    batches = _traced_batches(loader, lambda: num_updates)
    try:
        for batch_idx, batch_data in batches:
            if batch_idx < skip_batches:
                continue  # mid-epoch resume: already consumed before preemption
            if isinstance(batch_data, dict):
                # NaFlex dict batch; scalar metadata (seq_len/patch_size) stays on
                # host — the model derives the patch size from the patch dim shape
                n = batch_data['patches'].shape[0]
                if injector is not None and injector.nan_at(num_updates):
                    _logger.warning(f'[fault-inject] NaN batch at update {num_updates}')
                    batch_data = dict(batch_data, patches=np.asarray(batch_data['patches']) * np.nan)
                arrays = {k: v for k, v in batch_data.items() if k not in ('seq_len', 'patch_size')}
                seq = f'seq: {batch_data["seq_len"]} '
            else:
                input_np, target_np = batch_data
                if mixup_fn is not None:
                    input_np, target_np = mixup_fn(input_np, target_np)
                micro_inputs.append(input_np)
                micro_targets.append(target_np)
                if len(micro_inputs) < accum:
                    continue  # accumulate across loader batches (ref train.py:1266-1281)
                if accum > 1:
                    input_all = np.concatenate(micro_inputs, axis=0)
                    target_all = np.concatenate(micro_targets, axis=0)
                else:
                    input_all, target_all = micro_inputs[0], micro_targets[0]
                micro_inputs, micro_targets = [], []
                if injector is not None and injector.nan_at(num_updates):
                    _logger.warning(f'[fault-inject] NaN batch at update {num_updates}')
                    input_all = np.asarray(input_all) * np.nan
                n = input_all.shape[0]
                arrays = {'input': input_all, 'target': target_all}
                seq = ''
            batch = _batch_to_device(arrays, mesh, shard_batch)
            metrics = _or_rollback(
                task, partial(task.train_step, batch, lr=lr, step=num_updates), saver, rollback_budget)
            if metrics is None:
                update_idx += 1
                continue
            num_updates += 1
            samples_since_log += n
            if log_setup:
                _logger.info(_setup_line())
                log_setup = False
            _freeze_once()
            with tracing.span('train.bookkeeping'):
                if lr_scheduler is not None:
                    lr = lr_scheduler.step_update(num_updates)[0]
            if update_idx % args.log_interval == 0:
                with tracing.span('train.log_sync'):
                    loss_val = float(metrics['loss'])  # sync point
                if np.isfinite(loss_val):  # a skipped non-finite step must not poison the meter
                    loss_m.update(loss_val, n=n)
                elapsed = time.time() - log_t0
                ips = samples_since_log / max(elapsed, 1e-9)
                samples_since_log = 0
                log_t0 = time.time()
                nf = int(metrics['nonfinite_total']) if 'nonfinite_total' in metrics else 0
                host, log_counters = _host_line(log_since_ns, log_counters, metrics, expert_layers)
                log_since_ns = tracing.now_ns()
                if 'lm.tokens' in metrics:
                    # a sample is a sequence; the step's own count of its tokens gives tokens/s
                    seq += f"{ips * int(metrics['lm.tokens']) / n:.0f} tokens/s "
                _logger.info(
                    f'Train: {epoch} [{update_idx:>4d}/{updates_per_epoch}] '
                    f'Loss: {loss_m.val:#.3g} ({loss_m.avg:#.3g}) LR: {lr:.3e} '
                    f'{seq}{ips:.1f} img/s' + (f' NaN-skipped: {nf}' if nf else '') + f' {host}')
            with tracing.span('train.bookkeeping'):
                if saver is not None and args.recovery_interval and (update_idx + 1) % args.recovery_interval == 0:
                    drain()
                    saver.save_recovery(epoch, update_idx,
                                        extra_state=_recovery_extras(batch_idx + 1, num_updates, args))
                poll_faults_and_shutdown(batch_idx, update_idx)
            update_idx += 1
    finally:
        batches.close()  # the open `train.step` root ends here when an exception ends the epoch
    if micro_inputs:
        # flush trailing partial accumulation group: pad by wrapping samples so
        # the step shape stays static (slight duplicate weighting on the tail)
        input_all = np.concatenate(micro_inputs, axis=0)
        target_all = np.concatenate(micro_targets, axis=0)
        need = accum * micro_inputs[0].shape[0] - input_all.shape[0]
        if need > 0:
            reps = -(-need // input_all.shape[0])
            input_all = np.concatenate([input_all] + [input_all] * reps, axis=0)[:accum * micro_inputs[0].shape[0]]
            target_all = np.concatenate([target_all] + [target_all] * reps, axis=0)[:accum * micro_inputs[0].shape[0]]
        batch = _batch_to_device({'input': input_all, 'target': target_all}, mesh, shard_batch)
        metrics = _or_rollback(task, partial(task.train_step, batch, lr=lr, step=num_updates), saver, rollback_budget)
        if metrics is not None:
            num_updates += 1
            if lr_scheduler is not None:
                lr = lr_scheduler.step_update(num_updates)[0]
    # the epoch's last step, before evaluation and the epoch's checkpoint. Not in a `finally`: an epoch that
    # another exception ends (preemption after its recovery save, a closed window) reads nothing more
    drain()
    out = OrderedDict([('loss', loss_m.avg if loss_m.count else float((metrics or {}).get('loss', 0.0))), ('lr', lr)])
    if metrics and 'nonfinite_total' in metrics:
        out['nonfinite_steps'] = int(metrics['nonfinite_total'])
    return out


def _local_rows(arr):
    """Host-local rows of a (possibly) multi-process sharded array, in batch
    order. `float()`/eager jnp ops are illegal on non-fully-addressable
    arrays; metrics therefore reduce the ADDRESSABLE shards (deduped by
    replica_id, so tensor-parallel replication doesn't double-count) on host
    and cross-process-average at the end via `reduce_tensor`."""
    if not isinstance(arr, jax.Array) or arr.is_fully_addressable:
        return np.asarray(arr)
    shards = [s for s in arr.addressable_shards if s.replica_id == 0]
    shards.sort(key=lambda s: s.index[0].start or 0)
    return np.concatenate([np.asarray(s.data) for s in shards], axis=0)


def validate(task, loader, args, mesh, shard_batch, use_ema=False):
    """Eval loop. Each process scores its own addressable rows of the sharded
    eval output; per-process means are averaged across hosts at the end
    (every host sees the same batch count, so the mean-of-means is exact)."""
    from timm_tpu.parallel import reduce_tensor
    from timm_tpu.utils import AverageMeter
    loss_m = AverageMeter()
    top1_m = AverageMeter()
    top5_m = AverageMeter()
    for batch_data in loader:
        if isinstance(batch_data, dict):
            batch = shard_batch(
                {k: jnp.asarray(v) for k, v in batch_data.items() if k != 'seq_len'}, mesh)
            output = task.eval_step({k: batch[k] for k in batch if k != 'target'}, use_ema=use_ema)
            target = batch['target']
        else:
            input_np, target_np = batch_data
            batch = shard_batch({'input': jnp.asarray(input_np), 'target': jnp.asarray(target_np)}, mesh)
            if getattr(task.model, 'task_kind', None) in TOKEN_KINDS:
                # a language-model task scores on the device: sums over the batch's valid positions
                sums = {k: float(v) for k, v in task.eval_step(batch, use_ema=use_ema).items()}
                n = max(sums['count'], 1.0)
                loss_m.update(sums['loss_sum'] / n, n)
                top1_m.update(100.0 * sums['top1'] / n, n)
                top5_m.update(100.0 * sums['top5'] / n, n)
                continue
            output = task.eval_step({'input': batch['input']}, use_ema=use_ema)
            target = batch['target']
        out_np = _local_rows(output).astype(np.float32)
        tgt_np = _local_rows(target)
        if out_np.shape[0] == 0:
            continue
        shifted = out_np - out_np.max(axis=-1, keepdims=True)
        logprobs = shifted - np.log(np.exp(shifted).sum(axis=-1, keepdims=True))
        loss = -np.take_along_axis(logprobs, tgt_np[:, None], axis=-1).mean()
        top_pred = np.argsort(out_np, axis=-1)[:, -5:]
        correct1 = (top_pred[:, -1] == tgt_np).mean() * 100.0
        correct5 = (top_pred == tgt_np[:, None]).any(axis=-1).mean() * 100.0
        n = out_np.shape[0]
        loss_m.update(float(loss), n)
        top1_m.update(float(correct1), n)
        top5_m.update(float(correct5), n)
    return OrderedDict([('loss', float(reduce_tensor(loss_m.avg))),
                        ('top1', float(reduce_tensor(top1_m.avg))),
                        ('top5', float(reduce_tensor(top5_m.avg)))])


if __name__ == '__main__':
    try:
        main()
    except SystemExit as e:
        # Preemption/abort exits in a multi-process run must NOT run the
        # distributed client's atexit shutdown barrier: after a host loss it
        # raises a fatal C++ error that turns a clean exit-0 into SIGABRT.
        # Recovery state is already durable (the writer drained in main's
        # finally), so a hard exit loses nothing.
        if jax.process_count() > 1:
            logging.shutdown()
            sys.stdout.flush()
            sys.stderr.flush()
            os._exit(int(e.code or 0))
        raise
