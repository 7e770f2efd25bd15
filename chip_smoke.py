#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the repository's main path once on ONE TPU chip, through the entry
points a user calls, at the full width and depth of `vit_base_patch16_224`
(12 layers, d 768, 12 heads, MLP 3072, 197 tokens, 1000 classes, stochastic
depth 0.1), with random weights from a seed:

  device   jax.devices()[0].platform must be 'tpu' — there is no CPU branch
  train    `train.main([...])` in-process, twice: synthetic batches (the
           command line of README "Train"), then a seeded JPEG image folder
           through `create_loader(device_augment=True, device_prefetch=2)`
           with mixup, cutmix and random erasing on
  serve    `serve.InferenceEngine` with the default bucket ladder, AOT
           prewarm, bursts of requests checked against a plain jitted forward
  kernels  every registered Pallas kernel at its first live case, compiled
           (`tpu_custom_call` in the program text), against its XLA reference

`--chips 4` runs instead ONE sharded `ClassificationTask.train_step` on a
`create_mesh(fsdp=2, tp=2)` mesh and the same step on one device, and no
other phase.

Each phase prints one JSON line; any failed check raises and the process
exits non-zero at once. The seconds in those lines are smoke timings (wall
clock, compilation included) — not measurements of any metric. The last line
of stdout is `{"ok": true, "device": {...}}` as JAX reports the device.

Everything the run writes lands under `output/chip_smoke/` (git-ignored);
the compile cache is where `JAX_COMPILATION_CACHE_DIR` says, else the
checkout's `.jax_cache/`.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import shutil
import sys
import time

REPO_ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO_ROOT, 'output', 'chip_smoke')
SEED = 42

MODEL = 'vit_base_patch16_224'
DROP_PATH = 0.1
BATCH_SIZE = 128
SYNTHETIC_STEPS = 8
LOADER_STEPS = 4
SERVE_REQUESTS = 32

# Serve answers vs the plain jitted forward: both run the same float32 weights
# with the platform's default matmul precision (one bf16 pass on TPU, eps
# 2^-8), but as differently shaped programs (scan over a padded bucket vs a
# loop over the whole request batch), so accumulation order differs through
# 12 layers. 2e-2 of the largest |logit| is ~5 bf16 eps (4e-3 was seen on a
# v5e); float32 on the CPU agrees exactly.
SERVE_RTOL = 2e-2
# Sharded vs one-device step, bf16 compute: tensor-parallel matmuls split the
# contraction, so partial sums round differently before the all-reduce. Half a
# bf16 eps; 8e-5 was seen on four v5e chips and 8e-4 on four virtual CPU devices.
SHARDED_RTOL = 2e-3


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def check(cond, what: str):
    if not cond:
        raise SmokeFailure(what)


def _cache_counts(events) -> dict:
    from timm_tpu.utils.compile_cache import cache_event_total
    return {'compile_requests': cache_event_total(events, 'backend_compile_duration'),
            'cache_hits': cache_event_total(events, 'cache_hits'),
            'cache_misses': cache_event_total(events, 'cache_misses')}


def emit(phase: str, t0: float, checked: dict, events=None):
    import jax
    line = {'phase': phase, 'smoke_seconds': round(time.perf_counter() - t0, 1),
            'checked': checked}
    if events is not None:
        line['compile_cache'] = dict(dir=jax.config.jax_compilation_cache_dir,
                                     **_cache_counts(events))
    print(json.dumps(line), flush=True)


# -- device --------------------------------------------------------------------

def phase_device(chips: int) -> dict:
    """First thing: the platform must be a TPU with exactly `chips` devices.
    Sets no JAX_PLATFORMS and pins no platform."""
    t0 = time.perf_counter()
    import jax
    devices = jax.devices()
    device = {'platform': devices[0].platform, 'kind': devices[0].device_kind,
              'count': len(devices)}
    check(device['platform'] == 'tpu',
          f'no accelerator: jax.devices()[0].platform is {device["platform"]!r}, need "tpu"')
    check(device['count'] == chips, f'need {chips} chip(s), JAX reports {device["count"]}')
    import jaxlib
    emit('device', t0, dict(device, jax=jax.__version__, jaxlib=jaxlib.__version__))
    return device


# -- train ---------------------------------------------------------------------

@contextlib.contextmanager
def observe_train_steps():
    """Record every `ClassificationTask.train_step` that runs inside the
    block: the loss (read back, so the step has finished), the batch it got,
    and the running count of compilations at the end of the step — so
    compiles in the loader between steps are counted too. `step_program` is
    what the first call compiled or read from the cache; `before`/`after`
    are snapshots of the state the steps must carry."""
    import jax
    import numpy as np
    from flax import nnx

    from timm_tpu.task import ClassificationTask
    from timm_tpu.utils.compile_cache import collect_cache_events

    def small_leaves(tree):
        # biases, norm scales, tokens: cheap to copy, and exact zeros/ones at
        # init, so one AdamW step at warmup LR visibly moves every one
        return [np.array(x) for x in jax.tree.leaves(tree) if x.size <= 4096]

    def snapshot(task):
        return {'params': small_leaves(nnx.state(task.model, nnx.Param)),
                'ema': small_leaves(task.ema_params),
                'rng': [int(c) for c in jax.tree.leaves(nnx.state(task.model, nnx.RngCount))]}

    inner = ClassificationTask.train_step
    rec = {'losses': [], 'compiles': [], 'inputs': [], 'task': None}

    with collect_cache_events() as events:
        def train_step(self, batch, lr, step=0):
            if rec['task'] is None:
                rec['task'], rec['before'] = self, snapshot(self)
            rec['inputs'].append({k: (type(v).__name__, str(v.dtype), tuple(v.shape))
                                  for k, v in batch.items()})
            counts = _cache_counts(events)
            metrics = inner(self, batch, lr, step)
            rec['losses'].append(float(metrics['loss']))
            after = _cache_counts(events)
            rec['compiles'].append(after['compile_requests'])
            if 'step_program' not in rec:  # the first call builds the step program
                rec['step_program'] = {k: after[k] - counts[k] for k in after}
            return metrics

        ClassificationTask.train_step = train_step
        try:
            yield rec
        finally:
            ClassificationTask.train_step = inner
    if rec['task'] is not None:
        rec['after'] = snapshot(rec['task'])


def check_trainer_run(rec: dict, steps: int, what: str) -> dict:
    """The checks every trainer run must pass; returns what was seen."""
    import numpy as np
    n = len(rec['losses'])
    check(n >= steps, f'{what}: {n} train steps ran, need {steps}')
    check(all(math.isfinite(l) for l in rec['losses']), f'{what}: non-finite loss in {rec["losses"]}')
    # one program per run: a compile after the first step is a step program
    # traced twice (PR 21: unplaced sentinel counters did exactly that)
    late = rec['compiles'][-1] - rec['compiles'][0]
    check(late == 0, f'{what}: {late} compilation(s) after step 1 (running counts {rec["compiles"]})')
    before, after = rec['before'], rec['after']
    moved = [not np.array_equal(a, b) for a, b in zip(before['params'], after['params'])]
    check(moved and all(moved), f'{what}: {moved.count(False)} of {len(moved)} small parameter leaves did not change')
    # EMA moves by (1 - decay) of an already small update: below float32
    # resolution next to a 1.0 norm scale, exact next to a zero-init bias
    ema_moved = [not np.array_equal(a, b) for a, b in zip(before['ema'], after['ema']) if not a.any()]
    check(ema_moved and all(ema_moved), f'{what}: {ema_moved.count(False)} of {len(ema_moved)} zero-init EMA leaves did not change')
    advanced = [b - a for a, b in zip(before['rng'], after['rng']) if b != a]
    check(advanced and all(d == n for d in advanced),
          f'{what}: RNG counters not carried: advanced by {sorted(set(advanced))} over {n} steps')
    return {'steps': n, 'first_loss': round(rec['losses'][0], 4), 'last_loss': round(rec['losses'][-1], 4),
            'step_program': rec['step_program'],
            'compiles_after_step_1': late, 'param_leaves_changed': len(moved),
            'ema_leaves_changed': len(ema_moved), 'rng_counters_advanced': len(advanced)}


def write_image_folder(root: str, n_train: int, n_val: int, size: int = 256, classes: int = 8):
    """A seeded image-folder dataset of `size`-px JPEGs: smooth random colour
    fields, so decode + RandomResizedCrop see image-like content."""
    import numpy as np
    from PIL import Image
    rng = np.random.default_rng(SEED)
    for split, n in (('train', n_train), ('validation', n_val)):
        for i in range(n):
            d = os.path.join(root, split, f'class_{i % classes}')
            os.makedirs(d, exist_ok=True)
            coarse = rng.integers(0, 256, (8, 8, 3), dtype=np.uint8)
            Image.fromarray(coarse).resize((size, size), Image.BICUBIC).save(
                os.path.join(d, f'{i:05d}.jpg'), quality=90)


def phase_train(model: str = MODEL, batch_size: int = BATCH_SIZE,
                synthetic_steps: int = SYNTHETIC_STEPS, loader_steps: int = LOADER_STEPS,
                out_dir: str = OUT_DIR, extra_args=()):
    """`train.py`'s own `main` on synthetic batches, then on a seeded image
    folder through the device-augment loader. `extra_args` are further
    train.py arguments (the CPU rehearsal passes a small class count)."""
    t0 = time.perf_counter()
    import jax

    import train
    from timm_tpu.data.device_augment import batch_donate_argnums

    train_out = os.path.join(out_dir, 'train')
    common = ['--model', model, '-b', str(batch_size), '--amp', '--opt', 'adamw',
              '--model-ema', '--clip-grad', '1.0', '--drop-path', str(DROP_PATH),
              '--epochs', '1', '--seed', str(SEED), '--log-interval', '1',
              '--output', train_out, *extra_args]

    from timm_tpu.utils.compile_cache import collect_cache_events
    with collect_cache_events() as events:
        with observe_train_steps() as rec:
            train.main([*common, '--synthetic-data', '--experiment', 'synthetic',
                        '--synthetic-len', str(synthetic_steps * batch_size)])
        synthetic = check_trainer_run(rec, synthetic_steps, 'synthetic')
        num_classes = rec['task'].model.num_classes
        check(abs(rec['losses'][0] - math.log(num_classes)) <= 0.5,
              f'synthetic: first loss {rec["losses"][0]:.4f} not within ln({num_classes}) +- 0.5')

        data_dir = os.path.join(out_dir, 'imagefolder')
        shutil.rmtree(data_dir, ignore_errors=True)
        write_image_folder(data_dir, n_train=loader_steps * batch_size, n_val=batch_size)
        with observe_train_steps() as rec:
            train.main([*common, '--data-dir', data_dir, '--experiment', 'loader',
                        '--device-augment', '--device-prefetch', '2',
                        '--mixup', '0.8', '--cutmix', '1.0', '--reprob', '0.25'])
    loader = check_trainer_run(rec, loader_steps, 'loader')
    for seen in rec['inputs']:
        check(seen['input'][0].endswith('ArrayImpl') and seen['input'][1] == 'float32',
              f'loader: device-augment must hand the step device float batches, got {seen}')
        check(len(seen['target'][2]) == 2, f'loader: mixup soft targets expected, got {seen}')
    loader['batch_donate_argnums'] = list(batch_donate_argnums())
    if jax.default_backend() == 'tpu':
        check(loader['batch_donate_argnums'] == [0], 'loader: the augment program must donate its batch on TPU')

    shutil.rmtree(train_out)  # ViT-B checkpoints are GBs; the smoke keeps none
    emit('train', t0, {'model': model, 'batch_size': batch_size,
                       'synthetic': synthetic, 'loader': loader}, events)


# -- serve ---------------------------------------------------------------------

def phase_serve(model: str = MODEL, buckets=None, n_requests: int = SERVE_REQUESTS,
                model_kwargs=None):
    """An `InferenceEngine` with AOT prewarm answers bursts of requests; every
    answer is compared with a plain jitted `model.eval()(x)` on the same
    device. `buckets=None` is the engine's default ladder."""
    t0 = time.perf_counter()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax import nnx

    import timm_tpu
    from timm_tpu.serve import DEFAULT_BUCKETS, InferenceEngine
    from timm_tpu.utils.compile_cache import collect_cache_events, iter_jaxpr_eqns

    buckets = tuple(buckets or DEFAULT_BUCKETS)
    model_kwargs = dict(model_kwargs or {}, drop_path_rate=DROP_PATH)

    with collect_cache_events() as events:
        # max_wait_ms: a burst's requests are enqueued within a millisecond;
        # 50 ms keeps one burst one batch on a busy host, so every declared
        # bucket program is seen to run
        engine = InferenceEngine(buckets=buckets, max_wait_ms=50.0)
        engine.add_model(model, **model_kwargs)  # loads + AOT-compiles every bucket
    prewarm = dict(engine.stats['prewarm'][model])
    check(prewarm['programs'] == len(buckets), f'prewarm compiled {prewarm["programs"]} of {len(buckets)} buckets')

    # block_scan=True is the engine's default and must really be scanning: the
    # program the engine jits has a lax.scan as long as the block stack
    res = engine.pool.acquire(model)
    served = nnx.merge(res.graphdef, res.state)
    depth = len(served.blocks)
    h, w, c = res.input_size
    jaxpr = jax.make_jaxpr(lambda s, x: nnx.merge(res.graphdef, s)(x))(
        res.state, jax.ShapeDtypeStruct((1, h, w, c), jnp.float32))
    scans = [int(e.params['length']) for e in iter_jaxpr_eqns(jaxpr) if e.primitive.name == 'scan']
    check(depth in scans, f'block scan fell back to the loop: depth {depth}, scan lengths {scans}')

    # the plain reference: the same weights through a model that never heard
    # of the engine (loop over blocks), jitted once over all requests
    # (built abstractly: only its structure is needed, the weights are the engine's)
    plain = nnx.eval_shape(lambda: timm_tpu.create_model(model, **model_kwargs))
    plain.eval()
    plain_graphdef = nnx.split(plain)[0]
    reference = jax.jit(lambda s, x: nnx.merge(plain_graphdef, s)(x).astype(jnp.float32))

    # bursts sized so that every declared bucket runs at least once
    rng = np.random.default_rng(SEED)
    sizes, prev = [], 0
    for b in buckets:
        sizes.append(int(rng.integers(prev + 1, min(b, prev + 8) + 1)))
        prev = b
    while sum(sizes) < n_requests:
        sizes.append(int(rng.integers(1, buckets[-1] + 1)))
    images = rng.standard_normal((sum(sizes), h, w, c), dtype=np.float32)
    expected = np.asarray(reference(res.state, jnp.asarray(images)))

    answers = []
    with collect_cache_events() as serving_events, engine:
        start = 0
        for n in sizes:
            futures = [engine.submit(images[i]) for i in range(start, start + n)]
            answers.extend(f.result(timeout=300.0) for f in futures)
            start += n
    answers = np.stack(answers)

    stats = engine.snapshot_stats()
    check(stats['completed'] == len(images) and stats['failed'] == 0,
          f'serve: {stats["completed"]} completed, {stats["failed"]} failed of {len(images)}')
    check(answers.shape == expected.shape and np.isfinite(answers).all(),
          f'serve: answers {answers.shape} not finite logits of shape {expected.shape}')
    scale = float(np.abs(expected).max())
    diff = float(np.abs(answers - expected).max())
    check(diff <= SERVE_RTOL * scale, f'serve: max |answer - reference| {diff:.3e} > {SERVE_RTOL} * {scale:.3e}')
    compiles = _cache_counts(serving_events)['compile_requests']
    check(compiles == 0, f'serve: {compiles} compilation(s) after prewarm')
    unused = [b for b in buckets if not stats['steps_by_bucket'].get(b)]
    check(not unused, f'serve: bucket programs {unused} never ran (steps {stats["steps_by_bucket"]})')

    emit('serve', t0, {
        'model': model, 'buckets': list(buckets), 'burst_sizes': sizes, 'requests': len(images),
        'steps_by_bucket': {str(k): v for k, v in sorted(stats['steps_by_bucket'].items())},
        'block_scan_length': depth, 'max_abs_diff': diff, 'max_abs_logit': scale,
        'rtol': SERVE_RTOL, 'compiles_after_prewarm': compiles,
        'prewarm': {k: prewarm[k] for k in ('programs', 'cache_hits', 'fresh_compiles')}}, events)


# -- kernels -------------------------------------------------------------------

def phase_kernels(live: bool = True):
    """Each registered Pallas kernel once at its first declared case (`live`
    shapes on the chip, `dry` in the CPU rehearsal) against its registered
    XLA reference within the spec's parity_tol. On a TPU the kernel arm must
    be there as a `tpu_custom_call`, not interpreted."""
    t0 = time.perf_counter()
    import jax

    from timm_tpu.kernels import harness, registry
    from timm_tpu.utils.compile_cache import collect_cache_events, configure_compile_cache

    configure_compile_cache()
    seen = {}
    with collect_cache_events() as events:
        for spec in registry.all_specs():
            r = harness.parity_check(spec, spec.cases[0], seed=SEED, live=live)
            check(r['ok'], f'kernel {spec.name}/{r["case"]}: max err {r["max_abs_err"]:.3e} > tol {r["tol"]:.0e}')
            if jax.default_backend() == 'tpu':
                check(r['tpu_custom_call'], f'kernel {spec.name}: no tpu_custom_call in the compiled program')
            seen[spec.name] = {k: r[k] for k in ('case', 'max_abs_err', 'tol', 'tpu_custom_call')}
    check(seen, 'no kernel is registered')
    emit('kernels', t0, {'shapes': 'live' if live else 'dry', 'kernels': seen}, events)


# -- four chips ----------------------------------------------------------------

def phase_sharded(model: str = MODEL, batch_size: int = BATCH_SIZE, devices=None, model_kwargs=None):
    """ONE `ClassificationTask.train_step` on a `create_mesh(fsdp=2, tp=2)`
    mesh over four devices, and the same step (same seed, same batch) on one
    device. The mesh and model are built the way train.py builds them."""
    t0 = time.perf_counter()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax import nnx

    import timm_tpu
    from timm_tpu.data import resolve_data_config
    from timm_tpu.loss import LabelSmoothingCrossEntropy
    from timm_tpu.optim import create_optimizer_v2
    from timm_tpu.parallel import (
        create_mesh, create_sharded_model, param_bytes_per_device, set_global_mesh, shard_batch,
    )
    from timm_tpu.task import ClassificationTask
    from timm_tpu.utils.compile_cache import collect_cache_events, configure_compile_cache

    configure_compile_cache()
    devices = list(devices if devices is not None else jax.devices())
    check(len(devices) == 4, f'the sharded step needs 4 devices, got {len(devices)}')
    model_kwargs = dict(model_kwargs or {}, drop_path_rate=DROP_PATH, dtype=jnp.bfloat16, seed=SEED)

    def one_step(mesh):
        set_global_mesh(mesh)
        def build():
            return timm_tpu.create_model(model, **model_kwargs)

        sharded = 'fsdp' in mesh.axis_names or 'model' in mesh.axis_names
        net = create_sharded_model(build, mesh) if sharded else build()
        task = ClassificationTask(
            net, optimizer=create_optimizer_v2(net, opt='adamw', lr=1e-3, weight_decay=0.05),
            mesh=mesh, clip_grad=1.0, train_loss_fn=LabelSmoothingCrossEntropy(0.1))
        task.setup_ema(decay=0.9998)
        rng = np.random.RandomState(SEED)
        size = resolve_data_config({'img_size': model_kwargs.get('img_size')}, model=net)['input_size'][-1]
        batch = shard_batch({
            'input': jnp.asarray(rng.rand(batch_size, size, size, 3), jnp.float32),
            'target': jnp.asarray(rng.randint(0, net.num_classes, batch_size))}, mesh)
        metrics = task.train_step(batch, lr=1e-3, step=1)
        task.drain()  # the one step's non-finite counters: a step reads those of the step before it
        return task, float(metrics['loss']), float(metrics['grad_norm'])

    with collect_cache_events() as events:
        _, loss_1, gnorm_1 = one_step(create_mesh(devices=devices[:1]))
        mesh = create_mesh(devices=devices, fsdp=2, tp=2)
        task, loss_4, gnorm_4 = one_step(mesh)

    check(math.isfinite(loss_4) and math.isfinite(gnorm_4), f'sharded: loss {loss_4}, grad norm {gnorm_4}')
    check(abs(loss_4 - loss_1) <= SHARDED_RTOL * abs(loss_1), f'sharded loss {loss_4} vs one-device {loss_1}')
    check(abs(gnorm_4 - gnorm_1) <= SHARDED_RTOL * abs(gnorm_1), f'sharded grad norm {gnorm_4} vs one-device {gnorm_1}')

    # residency: every parameter a partition rule shards has a shard on each
    # of the four devices, of total/4 bytes (total/2 where one axis shards it)
    params = nnx.state(task.model, nnx.Param)
    per_device = {d.id: 0 for d in devices}
    n_sharded = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        for s in leaf.addressable_shards:
            per_device[s.device.id] += s.data.nbytes
        if leaf.sharding.is_fully_replicated:
            continue
        n_sharded += 1
        where = {s.device.id for s in leaf.addressable_shards}
        check(where == set(per_device), f'{jax.tree_util.keystr(path)}: shards on devices {sorted(where)} only')
        ways = {leaf.nbytes // s.data.nbytes for s in leaf.addressable_shards}
        check(ways <= {2, 4} and len(ways) == 1, f'{jax.tree_util.keystr(path)}: shard is 1/{sorted(ways)} of the leaf')
    check(n_sharded > 0, 'the partition rules sharded no parameter')
    replicated_bytes, sharded_bytes = param_bytes_per_device(params, mesh)
    check(len(set(per_device.values())) == 1, f'parameter bytes differ across devices: {per_device}')
    resident = next(iter(per_device.values()))
    check(resident == sharded_bytes, f'{resident} parameter bytes resident per device, rules say {sharded_bytes}')
    check(resident < 0.5 * replicated_bytes, f'{resident} of {replicated_bytes} bytes on each device: not sharded')

    emit('sharded_step', t0, {
        'model': model, 'batch_size': batch_size, 'mesh': dict(mesh.shape),
        'loss': {'one_device': loss_1, 'sharded': loss_4}, 'grad_norm': {'one_device': gnorm_1, 'sharded': gnorm_4},
        'rtol': SHARDED_RTOL, 'sharded_param_leaves': n_sharded,
        'param_mb_per_device': round(resident / 1e6, 2), 'param_mb_replicated': round(replicated_bytes / 1e6, 2)}, events)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    parser.add_argument('--chips', type=int, choices=(1, 4), default=1,
                        help='4: run only the sharded train step and its one-device twin')
    args = parser.parse_args(argv)

    device = phase_device(args.chips)
    if args.chips == 4:
        phase_sharded()
    else:
        shutil.rmtree(OUT_DIR, ignore_errors=True)
        phase_train()
        phase_serve()
        phase_kernels()
    print(json.dumps({'ok': True, 'device': device}), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
