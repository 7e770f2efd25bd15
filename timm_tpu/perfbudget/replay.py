"""Unattended replay of the PERF.md "next-round on-device checklist".

The checklist needed a human to type seven command families in order. This
module turns the whole queue into ONE scripted sequence:

    python bench.py --replay [--dry-run] [--save-self]

Every step is a REPLAY_STEPS entry with a `dry` spec (tiny models, CPU,
tier-1-smoked every run) and a `live` spec (the real on-device A/B). The two
specs run the IDENTICAL code path — only model size, batch, and step count
differ — so a live run executes a sequence that tier-1 has already proven end
to end. Results stream into BENCH_SELF.json (schema ``bench_self/v2``) after
EVERY step, so a run that dies mid-checklist still leaves everything measured
so far on disk.

This module also owns the BENCH_SELF.json v2 document helpers shared with
bench.py: the v2 file keeps the last live `result` (what `--save-self`
records) and the latest `replay` run. It is a record only: nothing reads a
result back to print it in place of a measurement. Top-level imports are
stdlib-only.
"""
from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ['REPLAY_STEPS', 'run_replay', 'load_self_doc', 'save_self_doc',
           'record_result', 'validate_self_result', 'SELF_SCHEMA']

SELF_SCHEMA = 'bench_self/v2'


# ---- BENCH_SELF.json v2 document ------------------------------------------

def load_self_doc(path: str) -> Dict:
    """Load (and, for pre-v2 files, upgrade) the BENCH_SELF document. A
    missing/corrupt file yields a fresh empty document."""
    doc: Dict = {}
    try:
        with open(path) as f:
            doc = json.load(f)
    except Exception:
        doc = {}
    if not isinstance(doc, dict):
        doc = {}
    if doc.get('schema') != SELF_SCHEMA:
        # v1 shape was {'measured_at', 'result'}; carry both forward
        doc = {'schema': SELF_SCHEMA,
               'measured_at': doc.get('measured_at'),
               'result': doc.get('result')}
    doc.setdefault('result', None)
    return doc


def save_self_doc(path: str, doc: Dict) -> None:
    tmp = path + '.tmp'
    with open(tmp, 'w') as f:
        json.dump(doc, f, indent=1)
        f.write('\n')
    os.replace(tmp, path)


def _now() -> str:
    return time.strftime('%Y-%m-%dT%H:%M:%SZ', time.gmtime())


def record_result(path: str, result: Dict) -> Dict:
    """`--save-self` success path: record the live measurement, preserving
    the last replay run."""
    doc = load_self_doc(path)
    doc['measured_at'] = _now()
    doc['result'] = result
    save_self_doc(path, doc)
    return doc


def validate_self_result(doc: Dict) -> List[str]:
    """Schema check for a v2 document; returns a list of problems (empty =
    valid). Used by the tier-1 dry-run smoke so a malformed writer can't
    silently produce an unparseable round file."""
    errs: List[str] = []
    if not isinstance(doc, dict):
        return ['document is not a JSON object']
    if doc.get('schema') != SELF_SCHEMA:
        errs.append(f"schema != {SELF_SCHEMA!r}: {doc.get('schema')!r}")
    result = doc.get('result')
    if result is not None and (not isinstance(result, dict) or 'value' not in result):
        errs.append('result present but not a bench result object')
    rep = doc.get('replay')
    if rep is not None:
        if not isinstance(rep, dict):
            errs.append('replay is not an object')
        else:
            for key in ('dry_run', 'steps', 'total', 'completed', 'failed'):
                if key not in rep:
                    errs.append(f'replay missing {key!r}')
            for i, s in enumerate(rep.get('steps', []) or []):
                if not isinstance(s, dict) or 'id' not in s or 'status' not in s:
                    errs.append(f'replay.steps[{i}] missing id/status')
                elif s['status'] not in ('ok', 'failed', 'skipped'):
                    errs.append(f"replay.steps[{i}] bad status {s['status']!r}")
    return errs


# ---- the checklist ----------------------------------------------------------
# One entry per PERF.md "next-round on-device checklist" family (`item` is
# the checklist number). `dry` and `live` feed the same runner.

_TINY = {'model': 'test_vit', 'img_size': 32, 'batch': 8,
         'model_kwargs': {'num_classes': 10}}
_VITB = {'model': 'vit_base_patch16_224', 'img_size': 224, 'batch': 128}

REPLAY_STEPS: Tuple[Dict, ...] = (
    dict(id='analysis', item=None, kind='analysis',
         title='static-analysis gate: source/jaxpr/HLO rules + zoo abstract-trace '
               '(a bench round never measures a repo the analyzers reject)',
         dry=dict(tiers=('A',), zoo='smoke'), live=dict()),
    dict(id='family_sweep', item=None, kind='family_sweep',
         title='family coverage sweep: re-derive the checked-in coverage matrix '
               '(abstract trace, stage/block scan, sharded donated step, serve '
               'AOT, device prefetch) and fail on any family that lost a '
               'capability (dry = the tier-1 smoke subset; live = every '
               'deep-eligible family)',
         dry=dict(families='smoke'), live=dict(families='all')),
    dict(id='baseline', item=1, kind='train',
         title='baseline train-step throughput (the --save-self measurement)',
         dry=dict(_TINY), live=dict(_VITB)),
    dict(id='donate_off', item=2, kind='train',
         title='donation A/B: --no-donate arm vs the baseline',
         dry=dict(_TINY, no_donate=True), live=dict(_VITB, no_donate=True)),
    dict(id='pad_auto', item=3, kind='train',
         title='token padding A/B: pad_tokens=auto (next sublane multiple)',
         dry=dict(_TINY, pad_tokens='auto'), live=dict(_VITB, pad_tokens='auto')),
    dict(id='pad_fixed', item=3, kind='train',
         title='token padding A/B: fixed pad (8 dry / 256 live) + masked softmax',
         dry=dict(_TINY, pad_tokens=8), live=dict(_VITB, pad_tokens=256)),
    dict(id='bf16_softmax', item=4, kind='train',
         title='bf16 softmax internals A/B',
         dry=dict(_TINY, softmax_dtype='bfloat16'),
         live=dict(_VITB, softmax_dtype='bfloat16')),
    dict(id='bf16_norm', item=4, kind='train',
         title='bf16 norm statistics A/B',
         dry=dict(_TINY, norm_dtype='bfloat16'),
         live=dict(_VITB, norm_dtype='bfloat16')),
    dict(id='bf16_mu', item=4, kind='train',
         title='bf16 optimizer first-moment A/B',
         dry=dict(_TINY, mu_dtype='bfloat16'), live=dict(_VITB, mu_dtype='bfloat16')),
    dict(id='bf16_all', item=4, kind='train',
         title='all three bf16 compute levers together',
         dry=dict(_TINY, softmax_dtype='bfloat16', norm_dtype='bfloat16',
                  mu_dtype='bfloat16'),
         live=dict(_VITB, softmax_dtype='bfloat16', norm_dtype='bfloat16',
                   mu_dtype='bfloat16')),
    dict(id='flash_gate', item=5, kind='flash',
         title='flash-attention masked-N gate: masked softmax path + kernel '
               'availability (win-at-N>=576-or-delete needs live hardware)',
         dry=dict(model='vit_tiny_patch16_224', img_size=64, batch=2,
                  pad_tokens=256),
         live=dict(model='naflexvit_base_patch16_gap', img_size=224, batch=32,
                   pad_tokens=784, pallas=True)),
    dict(id='grid_8x1', item=7, kind='train',
         title='fsdp x tp grid: (8,1)',
         dry=dict(_TINY, fsdp=8), live=dict(_VITB, batch=1024, fsdp=8)),
    dict(id='grid_4x2', item=7, kind='train',
         title='fsdp x tp grid: (4,2)',
         dry=dict(_TINY, fsdp=4, tp=2), live=dict(_VITB, batch=1024, fsdp=4, tp=2)),
    dict(id='grid_2x4', item=7, kind='train',
         title='fsdp x tp grid: (2,4)',
         dry=dict(_TINY, fsdp=2, tp=4), live=dict(_VITB, batch=1024, fsdp=2, tp=4)),
    dict(id='serve_drill', item=None, kind='serve',
         title='serving drill: continuous batching vs per-request at equal load',
         dry=dict(num_requests=128), live=dict(num_requests=1024)),
    dict(id='quant_serve', item=None, kind='quant_serve',
         title='int8 residency A/B: fp32 vs weight-only int8 under the same '
               'one-model HBM budget (int8 must hold both models, zero evictions)',
         dry=dict(num_requests=96), live=dict(num_requests=1024)),
    dict(id='device_augment', item=None, kind='train',
         title='on-device data path A/B: raw uint8 batch + jitted augment program '
               'fused into the step vs host-prepped floats (baseline step)',
         dry=dict(_TINY, device_augment=True),
         live=dict(_VITB, device_augment=True)),
    dict(id='kernels', item=5, kind='kernels',
         title='kernel portfolio win-or-delete A/B: every registered Pallas '
               'kernel vs its XLA reference at the declared regime shapes '
               '(dry = parity + pending gates on CPU; live = timed verdicts)',
         dry=dict(steps=3), live=dict(steps=20)),
    dict(id='naflex_bucketed', item=5, kind='naflex',
         title='NaFlex packed variable-resolution batches: zero fresh compiles over '
               'the seq-len bucket ladder after warmup (the flash masked-N>=576 '
               'experiment rides the same bucketed shapes)',
         dry=dict(model='test_naflexvit', seq_lens=(16, 25, 36), batch=4),
         live=dict(model='naflexvit_base_patch16_gap', seq_lens=(576, 784, 1024),
                   batch=16, pallas=True)),
    dict(id='autotune', item=None, kind='autotune',
         title='autotune top-K verification: rank the config space analytically, '
               'time the top-K predicted configs\' real steps, and fit the '
               'predicted->measured correction factor (live runs persist it to '
               'BENCH_SELF.json, where autotune.load_correction picks it up)',
         dry=dict(_TINY, global_batch=64, top_k=2, steps=2),
         live=dict(_VITB, global_batch=1024, top_k=3, steps=10)),
    dict(id='multihost', item=None, kind='multihost',
         title='multi-process pod drill: 2-process CPU cluster over '
               'jax.distributed, SIGKILL one host mid-epoch — survivor '
               'consensus + crash-safe manifest commit (dry = kill leg only; '
               'live adds the baseline-parity and elastic-resume legs)',
         dry=dict(processes=2, kill_update=4, compare=False, resume=False,
                  timeout=240),
         live=dict(processes=2, kill_update=4, compare=True, resume=True,
                   timeout=600)),
)


# ---- step runners -----------------------------------------------------------

def _build_tiny_step(spec: Dict):
    """Build a donated (unless no_donate) jitted train step for the spec's
    model/mesh, mirroring bench.py's measurement program. Returns
    (run_one_step, batch_size, meta) where run_one_step() advances the
    carried state and returns the loss."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from flax import nnx

    import timm_tpu
    from ..loss import cross_entropy
    from ..optim import create_optimizer_v2
    from ..parallel import (
        build_opt_shardings, build_param_shardings, create_mesh, set_global_mesh,
        shard_batch,
    )

    fsdp, tp = int(spec.get('fsdp', 0)), int(spec.get('tp', 0))
    if fsdp or tp:
        mesh = create_mesh(fsdp=fsdp or None, tp=tp or None)
    else:
        mesh = create_mesh(devices=jax.devices()[:1])
    set_global_mesh(mesh)

    model_kwargs = dict(spec.get('model_kwargs', {}))
    if spec.get('pad_tokens') is not None:
        model_kwargs['pad_tokens_to'] = spec['pad_tokens']
    model = timm_tpu.create_model(spec['model'], img_size=spec['img_size'],
                                  **model_kwargs)
    if hasattr(model, 'set_block_scan'):
        model.set_block_scan(True)
    model.train()
    opt_kwargs = {'mu_dtype': spec['mu_dtype']} if spec.get('mu_dtype') else {}
    opt = create_optimizer_v2(model, opt='adamw', lr=1e-3, weight_decay=0.05,
                              **opt_kwargs)
    graphdef, params, rest = nnx.split(model, nnx.Param, ...)
    param_sh = build_param_shardings(params, mesh)
    opt_sh, _ = build_opt_shardings(opt, params, mesh)
    params = jax.device_put(params, param_sh)
    opt_state = jax.jit(opt.init, out_shardings=opt_sh)(params)  # no-donate: init

    rng = np.random.RandomState(0)
    n = max(int(spec['batch']), mesh.size)
    s = spec['img_size']
    if spec.get('device_augment'):
        # on-device data path: raw uint8 batch + host-sampled params; the
        # jitted augment program runs fused inside the train step so its
        # per-step cost rides the A/B measurement
        import functools

        from ..data.device_augment import augment_image_batch
        raw = shard_batch({
            'image': jnp.asarray((rng.rand(n, s, s, 3) * 255).astype(np.uint8)),
            'target': jnp.asarray(rng.randint(0, model.num_classes, n)),
            'lam': jnp.asarray(rng.beta(0.8, 0.8, n), jnp.float32),
            'use_cutmix': jnp.zeros((n,), bool),
            'bbox': jnp.zeros((n, 4), jnp.int32)}, mesh)
        aug = functools.partial(augment_image_batch, mean=(0.5,) * 3, std=(0.5,) * 3,
                                num_classes=model.num_classes, smoothing=0.1)

        def batch_loss(m):
            xf, y = aug(raw)
            return -(y * jax.nn.log_softmax(m(xf))).sum(-1).mean()
    else:
        batch = shard_batch(
            {'x': jnp.asarray(rng.rand(n, s, s, 3), jnp.float32),
             't': jnp.asarray(rng.randint(0, model.num_classes, n))}, mesh)
        x, t = batch['x'], batch['t']

        def batch_loss(m):
            return cross_entropy(m(x), t)

    def train_step(p, o):
        def loss_fn(p):
            m = nnx.merge(graphdef, p, rest, copy=True)
            return batch_loss(m)
        loss, grads = jax.value_and_grad(loss_fn)(p)
        updates, o = opt.update(grads, o, p, lr=1e-3)
        return optax.apply_updates(p, updates), o, loss

    donate = () if spec.get('no_donate') else (0, 1)
    jitted = jax.jit(train_step, donate_argnums=donate,
                     in_shardings=(param_sh, opt_sh),
                     out_shardings=(param_sh, opt_sh, None))

    state = {'p': params, 'o': opt_state}

    def run_one_step():
        state['p'], state['o'], loss = jitted(state['p'], state['o'])
        return loss

    meta = {'model': spec['model'], 'batch': n,
            'mesh': 'x'.join(str(mesh.shape[a]) for a in mesh.axis_names),
            'donate': not spec.get('no_donate', False)}
    if spec.get('device_augment'):
        meta['device_augment'] = True
    for knob in ('pad_tokens', 'softmax_dtype', 'norm_dtype', 'mu_dtype'):
        if spec.get(knob) is not None:
            meta[knob] = spec[knob]
    return run_one_step, n, meta


@contextlib.contextmanager
def _precision_context(spec: Dict):
    """softmax/norm dtype policies are process-wide; the `with` form of the
    setters restores the previous value so arms can't leak into each other."""
    from ..layers import set_norm_internal_dtype, set_softmax_dtype
    with contextlib.ExitStack() as stack:
        if spec.get('softmax_dtype'):
            stack.enter_context(set_softmax_dtype(spec['softmax_dtype']))
        if spec.get('norm_dtype'):
            stack.enter_context(set_norm_internal_dtype(spec['norm_dtype']))
        yield


def _run_train(spec: Dict) -> Dict:
    import jax

    need = max(1, int(spec.get('fsdp', 0) or 1) * int(spec.get('tp', 0) or 1))
    if jax.device_count() < need:
        return {'status': 'skipped',
                'reason': f'needs {need} devices, have {jax.device_count()}'}
    with _precision_context(spec):
        run_one_step, n, meta = _build_tiny_step(spec)
        loss = run_one_step()  # warmup: compile + first step
        jax.block_until_ready(loss)
        steps = int(spec.get('steps', 2))
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = run_one_step()
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
    import math
    finite = math.isfinite(float(loss))
    out = dict(meta)
    out.update({'status': 'ok' if finite else 'failed',
                'img_per_s': round(n * steps / dt, 1),
                'steps': steps, 'loss_finite': finite})
    return out


def _run_flash(spec: Dict) -> Dict:
    """Checklist item 5 prerequisite drill: the masked-softmax path the
    N>=576 experiment rides (pad_tokens forces a key-padding mask through
    every attention) runs and stays finite; records whether the opt-in
    Pallas kernel is importable and whether its env gate is set. The
    win-or-delete decision itself needs live hardware."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax import nnx

    import timm_tpu
    from ..parallel import create_mesh, set_global_mesh

    set_global_mesh(create_mesh(devices=jax.devices()[:1]))
    model = timm_tpu.create_model(spec['model'], img_size=spec['img_size'],
                                  pad_tokens_to=spec['pad_tokens'])
    model.eval()
    graphdef, state = nnx.split(model)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(spec['batch'], spec['img_size'], spec['img_size'], 3),
                    jnp.float32)
    out = jax.jit(lambda s, xx: nnx.merge(graphdef, s)(xx))(state, x)
    finite = bool(jnp.isfinite(out).all())
    try:
        from ..kernels import flash_attention  # noqa: F401
        kernel_available = True
    except Exception:
        kernel_available = False
    return {'status': 'ok' if finite else 'failed',
            'model': spec['model'], 'masked_n': spec['pad_tokens'],
            'logits_finite': finite, 'pallas_kernel_importable': kernel_available,
            'pallas_env_gate': os.environ.get('TIMM_TPU_PALLAS_ATTN', ''),
            'live_needs': 'TIMM_TPU_PALLAS_ATTN=1 at masked N in {576, 784, 1024}'}


def _run_naflex(spec: Dict) -> Dict:
    """ISSUE-10 acceptance drill: donated NaFlex train steps over the declared
    seq-len bucket ladder, with the on-device augment program (normalize +
    token erase) ahead of each step. Epoch 1 warms one program per
    bucket; epoch 2 re-runs every bucket under compile-cache event collection
    and must observe ZERO fresh XLA compiles. The live spec additionally
    records the Pallas flash-attention gate state, since the masked-N>=576
    win-or-delete decision rides these same bucketed shapes."""
    import functools
    import math

    import jax
    import jax.numpy as jnp
    import numpy as np

    import timm_tpu
    from ..data.device_augment import augment_naflex_batch, batch_donate_argnums
    from ..optim import create_optimizer_v2
    from ..parallel import create_mesh, set_global_mesh
    from ..task import NaFlexClassificationTask
    from ..utils.compile_cache import cache_event_total, collect_cache_events

    set_global_mesh(create_mesh(devices=jax.devices()[:1]))
    model = timm_tpu.create_model(spec['model'], **spec.get('model_kwargs', {}))
    p = getattr(model.embeds, 'patch_size', 16)
    model.train()
    opt = create_optimizer_v2(model, opt='adamw', lr=1e-3, weight_decay=0.05)
    task = NaFlexClassificationTask(model, optimizer=opt)

    B = int(spec['batch'])
    buckets = tuple(spec['seq_lens'])
    # batch_donate_argnums: donated on accelerators, not on CPU — a donated
    # augment program deserialized from the persistent compile cache returns
    # corrupted buffers on XLA:CPU (fresh compiles are fine, so the poison
    # only bites the SECOND warm-cache process).
    aug = jax.jit(functools.partial(augment_naflex_batch, mean=(0.5,) * 3,
                                    std=(0.5,) * 3, re_mode='const'),
                  donate_argnums=batch_donate_argnums())

    def make_batch(seq_len, step):
        rng = np.random.RandomState(1000 * seq_len + step)
        gw = max(1, int(math.isqrt(seq_len)))
        gh = seq_len // gw
        n_tok = gh * gw  # natural grid <= bucket: padded slots stay invalid
        yy, xx = np.meshgrid(np.arange(gh), np.arange(gw), indexing='ij')
        patches = np.zeros((B, seq_len, p * p * 3), np.float32)
        coord = np.zeros((B, seq_len, 2), np.int32)
        valid = np.zeros((B, seq_len), bool)
        patches[:, :n_tok] = rng.rand(B, n_tok, p * p * 3)
        coord[:, :n_tok] = np.stack([yy, xx], -1).reshape(n_tok, 2)
        valid[:, :n_tok] = True
        erase = np.zeros((B, seq_len), bool)
        erase[:, :max(1, n_tok // 8)] = True
        return aug({'patches': jnp.asarray(patches),
                    'patch_coord': jnp.asarray(coord),
                    'patch_valid': jnp.asarray(valid),
                    'target': jnp.asarray(rng.randint(0, model.num_classes, B)),
                    'erase_mask': jnp.asarray(erase)})

    losses = []

    def run_epoch():
        for sl in buckets:
            metrics = task.train_step(make_batch(sl, len(losses)), lr=1e-3)
            losses.append(float(metrics['loss']))

    run_epoch()  # warmup epoch: one augment + one step program per bucket
    t0 = time.perf_counter()
    with collect_cache_events() as counts:
        run_epoch()
    dt = time.perf_counter() - t0
    misses = cache_event_total(counts, 'cache_misses')
    hits = cache_event_total(counts, 'cache_hits')
    finite = all(math.isfinite(v) for v in losses)
    out = {'status': 'ok' if (finite and misses == 0) else 'failed',
           'buckets': list(buckets), 'batch': B, 'patch_size': p,
           'warm_epoch_cache_misses': misses, 'warm_epoch_cache_hits': hits,
           'zero_recompile': misses == 0, 'loss_finite': finite,
           'warm_epoch_s': round(dt, 3)}
    if spec.get('pallas'):
        try:
            from ..kernels import flash_attention  # noqa: F401
            out['pallas_kernel_importable'] = True
        except Exception:
            out['pallas_kernel_importable'] = False
        out['pallas_env_gate'] = os.environ.get('TIMM_TPU_PALLAS_ATTN', '')
        out['live_needs'] = 'TIMM_TPU_PALLAS_ATTN=1 at masked N in {576, 784, 1024}'
    return out


def _run_serve(spec: Dict) -> Dict:
    import jax

    from ..parallel import create_mesh, set_global_mesh
    from ..serve import canonical_drill

    # the drill's engines run on a single-device mesh, and activation sharding
    # constraints resolve against the GLOBAL mesh — a leftover (fsdp, tp) mesh
    # from a grid step would poison every bucket program
    set_global_mesh(create_mesh(devices=jax.devices()[:1]))
    try:
        ab = canonical_drill(num_requests=int(spec['num_requests']),
                             persist_all_programs=True)
    except AssertionError as e:
        return {'status': 'failed', 'error': f'drill assertion: {e}'}
    c, b = ab['continuous'], ab['per_request']
    return {'status': 'ok', 'speedup': ab['speedup'],
            'continuous_img_per_s': c['img_per_s'], 'per_request_img_per_s': b['img_per_s'],
            'p50_ms': c['p50_ms'], 'p99_ms': c['p99_ms'],
            'evictions': c['evictions'], 'num_requests': c['num_requests']}


def _run_quant_serve(spec: Dict) -> Dict:
    import jax

    from ..parallel import create_mesh, set_global_mesh
    from ..serve import quant_residency_drill

    set_global_mesh(create_mesh(devices=jax.devices()[:1]))
    try:
        ab = quant_residency_drill(num_requests=int(spec['num_requests']),
                                   persist_all_programs=True)
    except AssertionError as e:
        return {'status': 'failed', 'error': f'drill assertion: {e}'}
    fp32, int8 = ab['fp32'], ab['int8']
    # the acceptance claim, asserted (not just recorded): under a budget that
    # holds ~1.25 fp32 models, the fp32 arm thrashed (3 LRU evictions for the
    # phase-split schedule) while the int8 arm held BOTH models resident with
    # zero evictions and zero failed requests — 2x residency, same budget
    if fp32['evictions'] < 3:
        return {'status': 'failed',
                'error': f"fp32 arm expected >=3 LRU evictions, saw {fp32['evictions']}"}
    return {'status': 'ok',
            'hbm_budget_bytes': ab['hbm_budget_bytes'],
            'fp32_evictions': fp32['evictions'],
            'int8_evictions': int8['evictions'],
            'int8_resident_models': ab['int8_resident'],
            'fp32_img_per_s': fp32['img_per_s'], 'int8_img_per_s': int8['img_per_s'],
            'int8_p99_ms': int8['p99_ms'], 'num_requests': int8['num_requests']}


def _run_kernels(spec: Dict, live: bool) -> Dict:
    """Kernel-portfolio win-or-delete A/B over the registry
    (kernels/harness.py). Parity always runs; on hardware a kernel did not
    claim (dry CPU arm for the TPU-only portfolio) its verdict is 'pending'
    — the gate settles on a live run on that hardware. A 'delete' verdict
    (parity failure, or a timed loss on claimed hardware) fails the step:
    the checklist refuses to carry a losing kernel forward."""
    from ..kernels.harness import format_verdict_line, run_kernel_ab

    verdicts = run_kernel_ab(live=live, steps=int(spec.get('steps', 5)))
    deletes = [r['kernel'] for r in verdicts if r['verdict'] == 'delete']
    return {'status': 'failed' if deletes else 'ok',
            'kernels': len(verdicts), 'delete': deletes,
            'verdicts': verdicts,
            'verdict_lines': [format_verdict_line(r) for r in verdicts]}


def _run_analysis(spec: Dict) -> Dict:
    """Static-analysis gate (timm_tpu/analysis) as a checklist step. The dry
    arm runs the Tier A source rules plus the zoo smoke subset (cheap, no
    probe lowering — tier-1 smokes it every run); the live arm runs EVERY
    rule, including the jaxpr/HLO passes over the freshly lowered probe
    programs. Any violation or analyzer error fails the step: the checklist
    refuses to measure a repo the analyzers reject."""
    from ..analysis import AnalysisContext, get, run_analysis, select
    from ..analysis.zoo import SMOKE_FAMILIES

    tiers = spec.get('tiers')
    rules = select(tiers=list(tiers) if tiers else None)
    zoo_families = None
    if spec.get('zoo') == 'smoke':
        rules = rules + [get('zoo-abstract-trace')]
        zoo_families = SMOKE_FAMILIES
    report = run_analysis(AnalysisContext(zoo_families=zoo_families), rules)
    return {'status': 'ok' if report.exit_code == 0 else 'failed',
            'exit_code': report.exit_code,
            'violations': len(report.violations),
            'waived': len(report.waived),
            'errors': report.errors,
            'rules': {n: r['status'] for n, r in report.rules.items()}}


def _run_autotune(spec: Dict, live: bool) -> Dict:
    """Verify the autotuner's predicted top-K against real step timings.

    Ranks the space analytically (the same zero-lowering tier the elastic
    re-solve uses), times the top-K distinct (fsdp, tp, batch) configs' real
    jitted steps via `_build_tiny_step` (measured global-step time =
    micro-step time x accum), and fits the predicted->measured correction
    factor as the geomean of the K ratios. Live runs hand the fitted factor
    back for persistence into BENCH_SELF.json ('_autotune_doc'); dry runs
    exercise the full path but never persist — a CPU-fitted factor must not
    leak into real solver runs."""
    import math
    import time as _time

    import jax

    from ..autotune import autotune

    model_kwargs = dict(spec.get('model_kwargs', {}))
    top_k = int(spec.get('top_k', 3))
    result = autotune(
        spec['model'], dict(model_kwargs, img_size=spec['img_size']),
        global_batch=int(spec['global_batch']),
        probe_anchor=False, correction=1.0,
        allow_remat=False, include_block_scan=False)

    # dedupe scan/remat variants: the timed step is always scanned, no remat
    chosen, seen = [], set()
    for rp in result.ranked:
        key = (rp.point.config.fsdp, rp.point.config.tp,
               rp.point.config.batch_size)
        if key not in seen:
            seen.add(key)
            chosen.append(rp)
        if len(chosen) >= top_k:
            break

    measured = []
    for rp in chosen:
        cfg = rp.point.config
        run_one_step, _n, _meta = _build_tiny_step(dict(
            spec, batch=cfg.batch_size, fsdp=cfg.fsdp if cfg.fsdp > 1 else 0,
            tp=cfg.tp if cfg.tp > 1 else 0))
        jax.block_until_ready(run_one_step())   # compile + warm
        t0 = _time.perf_counter()
        for _ in range(int(spec.get('steps', 3))):
            loss = run_one_step()
        jax.block_until_ready(loss)
        micro_ms = (_time.perf_counter() - t0) * 1e3 / int(spec.get('steps', 3))
        measured.append({'config': cfg.label(),
                         'predicted_ms': round(rp.cost.step_ms, 4),
                         'measured_ms': round(micro_ms * cfg.grad_accum, 4)})

    ratios = [m['measured_ms'] / m['predicted_ms'] for m in measured
              if m['predicted_ms'] > 0 and m['measured_ms'] > 0]
    correction = math.exp(sum(math.log(r) for r in ratios) / len(ratios)) \
        if ratios else 1.0
    by_measured = sorted(range(len(measured)),
                         key=lambda i: measured[i]['measured_ms'])
    out: Dict = {
        'tier': result.tier,
        'candidates': len(result.ranked),
        'top_k': [m['config'] for m in measured],
        'measured': measured,
        'winner_confirmed': bool(by_measured and by_measured[0] == 0),
        'correction': round(correction, 4),
    }
    if live:
        out['_autotune_doc'] = {'correction': out['correction'],
                                'fitted_at': _now(),
                                'model': spec['model'],
                                'global_batch': int(spec['global_batch']),
                                'measured': measured}
    return out


def _run_multihost(spec: Dict) -> Dict:
    """Run the host-loss kill drill (timm_tpu.resilience.multihost) as a bench
    step: real 2-process cluster bring-up, SIGKILL mid-epoch, survivor KV
    consensus, crash-safe manifest commit. A failed check fails the step."""
    import shutil

    from ..resilience.multihost import run_kill_drill

    workdir = spec.get('workdir') or os.path.join('output', 'replay', 'multihost')
    shutil.rmtree(workdir, ignore_errors=True)
    result = run_kill_drill(
        workdir,
        processes=int(spec.get('processes', 2)),
        kill_update=int(spec.get('kill_update', 4)),
        compare=bool(spec.get('compare', False)),
        resume=bool(spec.get('resume', False)),
        timeout=float(spec.get('timeout', 420)))
    if not result['ok']:
        failed = sorted(k for k, v in result['checks'].items() if not v)
        raise RuntimeError(
            f'kill drill failed checks {failed} (logs kept in {workdir})')
    if not spec.get('workdir'):
        shutil.rmtree(workdir, ignore_errors=True)
    return {'checks': result['checks'], 'details': result['details']}


def _run_family_sweep(spec: Dict) -> Dict:
    """Re-derive the family coverage matrix and diff it against the checked-in
    fixture (analysis/coverage.py). Any family whose measured capabilities
    drifted from tests/fixtures/coverage_matrix.json — a capability lost OR a
    new one left unpinned — fails the step, so a bench round never reports
    numbers for machinery the matrix says no longer works."""
    from ..analysis.coverage import (
        SMOKE_COVERAGE_FAMILIES, diff_matrix, family_coverage, load_matrix,
    )

    families = None
    if spec.get('families') == 'smoke':
        families = list(SMOKE_COVERAGE_FAMILIES)
    rows = family_coverage(families=families)
    problems = diff_matrix(load_matrix()['families'], rows)
    if problems:
        raise RuntimeError('coverage matrix drift:\n' + '\n'.join(problems))
    deep = [m for m, r in rows.items() if r['deep']]
    return {'families': len(rows), 'deep': len(deep),
            'green': sum(1 for m in deep
                         if rows[m]['sharded_donated_step'] and rows[m]['serve_aot']),
            'scan_capable': sum(1 for r in rows.values()
                                if r['stage_or_block_scan'])}


def _run_step(step: Dict, dry_run: bool) -> Dict:
    spec = step['dry'] if dry_run else step['live']
    if step['kind'] == 'analysis':
        return _run_analysis(spec)
    if step['kind'] == 'family_sweep':
        return _run_family_sweep(spec)
    if step['kind'] == 'train':
        return _run_train(spec)
    if step['kind'] == 'flash':
        return _run_flash(spec)
    if step['kind'] == 'serve':
        return _run_serve(spec)
    if step['kind'] == 'quant_serve':
        return _run_quant_serve(spec)
    if step['kind'] == 'naflex':
        return _run_naflex(spec)
    if step['kind'] == 'kernels':
        return _run_kernels(spec, live=not dry_run)
    if step['kind'] == 'autotune':
        return _run_autotune(spec, live=not dry_run)
    if step['kind'] == 'multihost':
        return _run_multihost(spec)
    raise ValueError(f"unknown replay step kind {step['kind']!r}")


def run_replay(dry_run: bool = True, self_path: Optional[str] = None,
               names: Optional[Sequence[str]] = None,
               log=None) -> Tuple[Dict, int]:
    """Execute the checklist (all steps, or the `names` subset) and persist
    the run into BENCH_SELF.json after EVERY step. Returns (replay_doc,
    exit_code); exit_code is 0 iff no step failed."""
    from ..parallel import mesh as mesh_mod

    steps = list(REPLAY_STEPS)
    if names is not None:
        wanted = set(names)
        unknown = wanted - {s['id'] for s in steps}
        if unknown:
            raise ValueError(f'unknown replay step(s): {sorted(unknown)}')
        steps = [s for s in steps if s['id'] in wanted]

    replay_doc: Dict = {'dry_run': bool(dry_run), 'started_at': _now(),
                        'steps': [], 'total': len(steps),
                        'completed': 0, 'failed': 0, 'skipped': 0}
    autotune_doc: Dict = {}

    def persist():
        if self_path:
            doc = load_self_doc(self_path)
            doc['replay'] = replay_doc
            if autotune_doc:
                # the live autotune step's fitted correction factor —
                # autotune.load_correction reads it on every later solve
                doc['autotune'] = autotune_doc
            save_self_doc(self_path, doc)

    persist()
    saved_mesh = mesh_mod.peek_global_mesh()
    try:
        for step in steps:
            t0 = time.perf_counter()
            rec: Dict = {'id': step['id'], 'item': step['item'], 'title': step['title']}
            try:
                result = _run_step(step, dry_run)
                autotune_doc.update(result.pop('_autotune_doc', {}))
                rec['status'] = result.pop('status', 'ok')
                key = 'reason' if rec['status'] == 'skipped' else 'result'
                rec[key] = result.get('reason') if rec['status'] == 'skipped' else result
            except Exception as e:
                rec['status'] = 'failed'
                rec['error'] = f'{type(e).__name__}: {e}'
            rec['wall_s'] = round(time.perf_counter() - t0, 2)
            replay_doc['steps'].append(rec)
            replay_doc['completed' if rec['status'] == 'ok' else
                       ('skipped' if rec['status'] == 'skipped' else 'failed')] += 1
            persist()
            if log is not None:
                log(f"replay {step['id']} [{rec['status']}] {rec['wall_s']}s")
    finally:
        mesh_mod._GLOBAL_MESH = saved_mesh
    replay_doc['finished_at'] = _now()
    persist()
    return replay_doc, (0 if replay_doc['failed'] == 0 else 2)
