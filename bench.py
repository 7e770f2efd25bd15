#!/usr/bin/env python3
"""Driver benchmark: prints JSON status lines; on success the LAST line is the
result `{"metric": ..., "value": N, "unit": ..., "vs_baseline": N}`.

Headline: ViT-B/16 @224 train-step throughput (img/s/chip), bf16, batch 128
per chip, AdamW — vs the reference's published train throughput for the same
model (BASELINE.md: 393.0 img/s, RTX 3090 AMP NHWC).

Methodology: K steps are fused into ONE XLA program (lax.scan carrying
params/opt-state), so the measurement is pure device time — host dispatch and
transfer latency are excluded, matching how the reference's CUDA-event timing
excludes host overhead (benchmark.py:149-157).

The measurement runs in the process it starts in (a chip belongs to one
process). Where the platform is not `tpu`, or the device kind has no entry in
CHIP_PEAK, it exits non-zero and prints no number: there is no CPU fallback
and no result from an earlier run is ever printed. The CPU-only modes
(--dry-run, --compile-report, --serve/--replay/--kernels/--analysis with
--dry-run) say so in their help.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

BASELINES = {
    ('vit_base_patch16_224', 'train'): 393.0,
    ('vit_base_patch16_224', 'infer'): 3915.6,
    ('vit_tiny_patch16_224', 'train'): 2299.6,
    ('vit_tiny_patch16_224', 'infer'): 26140.3,
    ('convnext_base', 'train'): 338.7,
    ('convnext_base', 'infer'): 2618.0,
    ('efficientnetv2_s', 'train'): 559.2,
    ('efficientnetv2_s', 'infer'): 3683.6,
}

# bf16 peak FLOP/s per chip for MFU reporting
CHIP_PEAK = {'v5e': 197e12, 'v5litepod': 197e12, 'v4': 275e12, 'v5p': 459e12, 'v6e': 918e12}

_REPO_ROOT = os.path.dirname(os.path.abspath(__file__))

SELF_RESULT_PATH = os.environ.get(
    'TIMM_TPU_BENCH_SELF', os.path.join(_REPO_ROOT, 'BENCH_SELF.json'))

_START = time.time()


def _chip_peak(device_kind: str) -> float:
    """bf16 peak of the chip JAX reports; a device that is not in CHIP_PEAK is
    an error, not a default."""
    kind = device_kind.lower().replace(' ', '').replace('tpu', '')
    for name, peak in CHIP_PEAK.items():
        if kind and (name in kind or kind in name):
            return peak
    raise KeyError(f'no peak FLOP/s known for device kind {device_kind!r} '
                   f'(have {sorted(CHIP_PEAK)})')


def _status(phase: str, **extra):
    """Print a status line that is ALSO a valid result schema, so that if the
    driver kills us right now its recorded tail still parses."""
    d = {'metric': f'bench status: {phase} (t+{time.time() - _START:.0f}s)',
         'value': 0.0, 'unit': 'img/s/chip', 'vs_baseline': None}
    d.update(extra)
    print(json.dumps(d), flush=True)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument('--model', default='vit_base_patch16_224')
    parser.add_argument('--bench', default='train', choices=['train', 'infer'])
    parser.add_argument('--batch-size', type=int, default=None)
    parser.add_argument('--img-size', type=int, default=224)
    parser.add_argument('--steps', type=int, default=10)
    parser.add_argument('--fast', action='store_true', help='small model / few steps smoke mode')
    # --- TPU alignment / precision A/B levers (PERF.md checklist items 3-4).
    # All default OFF = exact pre-PR numerics; each is independent.
    parser.add_argument('--pad-tokens', default='',
                        help="tile-align the ViT token count: 'auto' (next sublane "
                             "multiple, 197→200), an int (e.g. 256), or '' = off")
    parser.add_argument('--softmax-dtype', default='',
                        help="attention softmax internals: 'bfloat16' = fp32 max-"
                             "subtraction + bf16 exp/normalize, '' = legacy fp32")
    parser.add_argument('--norm-dtype', default='',
                        help="LayerNorm/RmsNorm statistics dtype: 'bfloat16' or '' = fp32")
    parser.add_argument('--mu-dtype', default='',
                        help="optimizer first-moment dtype: 'bfloat16' halves m HBM "
                             "traffic (v stays fp32), '' = fp32")
    parser.add_argument('--quantize', default='', choices=['', 'int8'],
                        help="serve-path weight quantization A/B: 'int8' runs the "
                             'measurement (--bench infer) against weight-only int8 '
                             'params with dequant fused at use; also smoked by '
                             "--dry-run. '' = dense weights")
    parser.add_argument('--block-scan', action='store_true', default=False,
                        help='scan-over-layers block execution: one lax.scan over '
                             'stacked per-layer params (O(1)-in-depth trace/compile)')
    parser.add_argument('--device-augment', action='store_true', default=False,
                        help='A/B the on-device data path: the train batch stays raw '
                             'uint8 with host-sampled augment params, and the jitted '
                             'normalize + mixup + erase program runs fused ahead of '
                             'every step (data/device_augment.py)')
    parser.add_argument('--fsdp', type=int, default=0, metavar='N',
                        help='shard params + optimizer state over an N-way fsdp mesh '
                             "axis (ZeRO-style; mesh becomes ('data', 'fsdp')); 0 = off")
    parser.add_argument('--tp', type=int, default=0, metavar='N',
                        help="tensor parallelism: N-way 'model' mesh axis sharding "
                             'attention heads + MLP hidden, with activation sharding '
                             'constraints on the residual stream; composes with --fsdp '
                             "(mesh becomes ('data'[, 'fsdp'], 'model')); 0 = off")
    parser.add_argument('--no-donate', action='store_true', default=False,
                        help='disable buffer donation of params/opt state in the jitted '
                             'step (A/B the input-output aliasing win)')
    parser.add_argument('--compile-report', action='store_true', default=False,
                        help='CPU compile-cost report: cold trace ms / cold compile ms / '
                             'warm-disk-cache ms / jaxpr equation counts, scan off vs on '
                             '(4 fresh CPU child processes; this parent never touches JAX)')
    parser.add_argument('--compile-child', action='store_true',
                        help='internal: run one compile-cost measurement in this process')
    parser.add_argument('--dry-run', action='store_true',
                        help='in-process smoke: build the model + one tiny train/infer '
                             'step with the requested levers, print an ok/failed line '
                             '(unit "ok", never a rate), exit.')
    parser.add_argument('--fault-inject', default='', metavar='SPEC',
                        help='(with --dry-run) also run the resilience fault-injection '
                             'selftest: truncated-checkpoint fallback, reader retry/backoff, '
                             'poison-skip budget, @-step faults incl. elastic resize@N:D '
                             '(fire-once parse + device-count capture). SPEC is '
                             'parse-checked; the canonical drill set always runs '
                             '(tier-1 smoke, no TPU).')
    parser.add_argument('--serve', action='store_true',
                        help='run the serving load drill instead of a train/infer bench: '
                             'canonical continuous-batching vs per-request A/B (two models, '
                             'two buckets, one LRU eviction) on synthetic open-loop Poisson '
                             'traffic, reporting p50/p99 latency and img/s. CPU-runnable; '
                             'combine with --dry-run for the tier-1 smoke.')
    parser.add_argument('--serve-requests', type=int, default=256, metavar='N',
                        help='(with --serve) requests per drill arm')
    parser.add_argument('--replay', action='store_true',
                        help='execute the entire queued PERF.md A/B checklist (donation, '
                             'pad-tokens, bf16 knobs, fsdp x tp grid, flash gate, '
                             'serve drill) as one scripted sequence, recording every '
                             'step into BENCH_SELF.json. Combine with --dry-run for the '
                             'tier-1 CPU smoke (tiny models, same code path).')
    parser.add_argument('--replay-steps', default='', metavar='A,B',
                        help='(with --replay) comma-separated subset of step ids')
    parser.add_argument('--kernels', action='store_true',
                        help='kernel portfolio win-or-delete A/B: run every registered '
                             'Pallas kernel (timm_tpu/kernels/registry.py) against its '
                             'XLA reference at the declared regime shapes and print one '
                             'keep/delete/pending verdict line per kernel, recording the '
                             'verdicts into BENCH_SELF.json. Combine with --dry-run for '
                             'the tier-1 CPU smoke (parity always runs; timed verdicts '
                             'settle on the claimed hardware). Also runs as the replay '
                             "checklist's `kernels` step.")
    parser.add_argument('--analysis', action='store_true',
                        help='run the static-analysis suite (timm_tpu/analysis: source/'
                             'jaxpr/HLO rules + zoo abstract-trace) and record the report '
                             'into BENCH_SELF.json. Combine with --dry-run for the cheap '
                             'arm (Tier A source rules + zoo smoke, no probe lowering) — '
                             "the same spec the replay checklist's `analysis` step smokes "
                             'in tier-1; the full run also walks the jaxpr/HLO of every '
                             'probe program. Exit 0 clean / 2 violations / 3 analyzer '
                             'error.')
    parser.add_argument('--save-self', action='store_true',
                        help='on success, record result to BENCH_SELF.json')
    args = parser.parse_args()
    if (args.quantize and args.bench == 'train'
            and not (args.dry_run or args.serve or args.replay
                     or args.compile_report)):
        parser.error('--quantize int8 quantizes weights for the serve path; '
                     'measure it with --bench infer (or smoke with --dry-run)')
    if args.fast:
        args.model = 'vit_tiny_patch16_224'
        args.steps = 5

    if args.compile_child:
        raise SystemExit(_compile_child(args))

    if args.compile_report:
        raise SystemExit(_compile_report(args))

    if args.replay:
        raise SystemExit(_replay_checklist(args))

    if args.kernels:
        raise SystemExit(_kernels_ab(args))

    if args.analysis:
        raise SystemExit(_analysis(args))

    if args.serve:
        raise SystemExit(_serve_drill(args))

    if args.dry_run:
        raise SystemExit(_dry_run(args))

    raise SystemExit(_measure(args))


def _apply_precision_knobs(args):
    """Activate the requested alignment/precision levers process-wide and
    return (model_kwargs, opt_kwargs, tag) for the run. Every lever defaults
    off → this is a no-op returning empty kwargs and '' tag."""
    from timm_tpu.layers import set_norm_internal_dtype, set_softmax_dtype
    model_kwargs, opt_kwargs, tags = {}, {}, []
    if args.pad_tokens:
        pad = args.pad_tokens if args.pad_tokens == 'auto' else int(args.pad_tokens)
        model_kwargs['pad_tokens_to'] = pad
        tags.append(f'pad_tokens={args.pad_tokens}')
    if args.softmax_dtype:
        set_softmax_dtype(args.softmax_dtype)
        tags.append(f'softmax={args.softmax_dtype}')
    if args.norm_dtype:
        set_norm_internal_dtype(args.norm_dtype)
        tags.append(f'norm={args.norm_dtype}')
    if args.mu_dtype:
        opt_kwargs['mu_dtype'] = args.mu_dtype
        tags.append(f'mu={args.mu_dtype}')
    return model_kwargs, opt_kwargs, (' [' + ', '.join(tags) + ']' if tags else '')


def _dry_run(args) -> int:
    """CPU smoke path for the A/B levers: builds the model with the requested
    knobs and runs one tiny train + infer step in-process. Exists so every
    flag combination has a fast correctness gate that needs no TPU
    (tests/test_precision_policy.py sweeps it)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from flax import nnx

    import timm_tpu
    from timm_tpu.loss import cross_entropy
    from timm_tpu.optim import create_optimizer_v2
    from timm_tpu.parallel import (
        build_opt_shardings, build_param_shardings, create_mesh, set_global_mesh, shard_batch,
    )
    from timm_tpu.utils import configure_compile_cache

    configure_compile_cache()
    # single-device mesh unless --fsdp/--tp is being smoked: SPMD-partitioning
    # the tiny dry-run program over every visible device multiplies its compile
    # cost for no extra coverage (the flag-combination sweep runs 9 of these)
    fsdp = getattr(args, 'fsdp', 0)
    tp = getattr(args, 'tp', 0)
    if fsdp or tp:
        mesh = create_mesh(fsdp=fsdp or None, tp=tp or None)
    else:
        mesh = create_mesh(devices=jax.devices()[:1])
    set_global_mesh(mesh)
    model_kwargs, opt_kwargs, tag = _apply_precision_knobs(args)
    img = min(args.img_size, 64)  # tiny input: the gate is "traces + runs", not perf
    model = timm_tpu.create_model(args.model, img_size=img, **model_kwargs)
    if getattr(args, 'block_scan', False) and hasattr(model, 'set_block_scan'):
        model.set_block_scan(True)
        tag += ' [block_scan]'
    if getattr(args, 'fsdp', 0):
        tag += f' [fsdp={args.fsdp}]'
    if getattr(args, 'tp', 0):
        tag += f' [tp={args.tp}]'
    if getattr(args, 'no_donate', False):
        tag += ' [no-donate]'
    rng = np.random.RandomState(0)
    n = max(2, mesh.size)  # batch must divide over the mesh batch axes
    if getattr(args, 'device_augment', False):
        import functools

        from timm_tpu.data.device_augment import augment_image_batch
        tag += ' [device_augment]'
        raw = shard_batch({
            'image': jnp.asarray((rng.rand(n, img, img, 3) * 255).astype(np.uint8)),
            'target': jnp.asarray(rng.randint(0, model.num_classes, n)),
            'lam': jnp.full((n,), 0.7, jnp.float32),
            'use_cutmix': jnp.zeros((n,), bool),
            'bbox': jnp.zeros((n, 4), jnp.int32)}, mesh)
        aug_fn = functools.partial(
            augment_image_batch, mean=(0.5,) * 3, std=(0.5,) * 3,
            num_classes=model.num_classes, smoothing=0.1)
        x, y_soft = jax.jit(aug_fn)(raw)  # not donated: x feeds the eval pass too

        def loss_for(m):
            # soft-target CE mirrors the device-mixup train path
            return -(y_soft * jax.nn.log_softmax(m(x))).sum(-1).mean()
    else:
        batch = shard_batch({'x': jnp.asarray(rng.rand(n, img, img, 3), jnp.float32),
                             't': jnp.asarray(rng.randint(0, model.num_classes, n))}, mesh)
        x, t = batch['x'], batch['t']

        def loss_for(m):
            return cross_entropy(m(x), t)

    model.train()
    opt = create_optimizer_v2(model, opt='adamw', lr=1e-3, weight_decay=0.05, **opt_kwargs)
    graphdef, params, rest = nnx.split(model, nnx.Param, ...)
    param_sh = build_param_shardings(params, mesh)
    opt_sh, _ = build_opt_shardings(opt, params, mesh)
    params = jax.device_put(params, param_sh)
    opt_state = jax.jit(opt.init, out_shardings=opt_sh)(params)  # no-donate: init

    def train_step(p, o):
        def loss_fn(p):
            m = nnx.merge(graphdef, p, rest, copy=True)
            return loss_for(m)
        loss, grads = jax.value_and_grad(loss_fn)(p)
        updates, o = opt.update(grads, o, p, lr=1e-3)
        return optax.apply_updates(p, updates), o, loss

    donate = () if getattr(args, 'no_donate', False) else (0, 1)
    params, opt_state, loss = jax.jit(
        train_step, donate_argnums=donate,
        in_shardings=(param_sh, opt_sh), out_shardings=(param_sh, opt_sh, None))(params, opt_state)
    model = nnx.merge(graphdef, params, rest)
    model.eval()
    logits = model(x)
    ok = bool(jnp.isfinite(loss)) and bool(jnp.isfinite(logits).all())
    quant_note = ''
    if getattr(args, 'quantize', ''):
        # int8 arm: quantize the just-trained eval state and run the same
        # batch through the dequant-at-use program; the gate is "stays finite
        # and tracks the fp32 logits", the tight tolerance lives in tier-1
        from timm_tpu.quantize import dequantize_tree, quantize_tree
        tag += ' [quant=int8]'
        gd_e, st_e = nnx.split(model)
        qstate = quantize_tree(st_e)
        qlogits = jax.jit(
            lambda q, xx: nnx.merge(gd_e, dequantize_tree(q))(xx))(qstate, x)
        qdiff = float(jnp.max(jnp.abs(qlogits.astype(jnp.float32)
                                      - logits.astype(jnp.float32))))
        ok = ok and bool(jnp.isfinite(qlogits).all())
        quant_note = f', int8 logits max|d|={qdiff:.4f}'
    fault_note = ''
    if getattr(args, 'fault_inject', ''):
        # exercise the injection hooks + their recovery paths without a slow
        # run: truncate→fallback, io_error→retry, poison budget, @-faults
        from timm_tpu.resilience import fault_selftest
        drill = fault_selftest(getattr(args, 'fault_inject', ''))
        ok = ok and drill['ok']
        failed = [k for k, v in drill['checks'].items() if not v]
        fault_note = (f', fault-inject drills {"all passed" if drill["ok"] else f"FAILED: {failed}"}'
                      f' ({len(drill["checks"])} checks)')
    print(json.dumps({
        'metric': f'dry-run {args.model}{tag}: 1 train step + 1 infer step on '
                  f'{jax.default_backend()}, loss finite={ok}{quant_note}{fault_note}',
        'value': 1.0 if ok else 0.0, 'unit': 'ok', 'vs_baseline': None}), flush=True)
    return 0 if ok else 2


def _serve_drill(args) -> int:
    """Canonical serving A/B drill (ISSUE 8 acceptance): the SAME open-loop
    Poisson schedule against the continuous-batching engine (buckets (4, 16),
    two models under an HBM budget that forces one LRU eviction) and the
    per-request baseline (bucket (1,), zero wait). Prints the human p50/p99
    summary line, then the JSON result line whose value is the img/s speedup.
    CPU-runnable end to end — wired like the --fault-inject drill smoke."""
    from timm_tpu.serve import canonical_drill, summary_line

    _status('serve drill: continuous-batching vs per-request A/B')
    t0 = time.perf_counter()
    try:
        ab = canonical_drill(num_requests=args.serve_requests,
                             persist_all_programs=True)
    except AssertionError as e:
        print(json.dumps({
            'metric': f'serve drill FAILED: {e}',
            'value': 0.0, 'unit': 'x img/s vs per-request', 'vs_baseline': None}),
            flush=True)
        return 2
    c, b = ab['continuous'], ab['per_request']
    print(summary_line(ab), flush=True)
    print(json.dumps({
        'metric': (f'serve drill: continuous-batching img/s vs per-request at equal '
                   f'offered load ({c["num_requests"]} reqs @ {c["offered_rps"]} req/s; '
                   f'continuous p50 {c["p50_ms"]}ms p99 {c["p99_ms"]}ms, '
                   f'per-request p50 {b["p50_ms"]}ms p99 {b["p99_ms"]}ms; '
                   f'buckets {tuple(c["buckets"])}, {c["evictions"]} eviction(s), '
                   f'{time.perf_counter() - t0:.1f}s wall)'),
        'value': ab['speedup'], 'unit': 'x img/s vs per-request',
        'vs_baseline': None}), flush=True)
    return 0


def _force_cpu_topology():
    """The fsdp x tp replay steps need 8 devices; a CPU host only
    grows them if the XLA flag is exported before jax's FIRST import (no-op
    once jax is loaded, and harmless on a real TPU backend)."""
    if 'jax' in sys.modules:
        return
    flags = os.environ.get('XLA_FLAGS', '')
    if '--xla_force_host_platform_device_count' not in flags:
        os.environ['XLA_FLAGS'] = (
            flags + ' --xla_force_host_platform_device_count=8').strip()


def _replay_checklist(args) -> int:
    """The whole queued PERF.md "next-round on-device checklist" as ONE
    unattended scripted sequence (timm_tpu.perfbudget.replay). --dry-run is
    the tier-1 CPU smoke over the identical code path; live mode is the
    on-device run. Either way every step's record streams into
    BENCH_SELF.json as it lands, so a run killed mid-checklist keeps
    everything measured so far."""
    _force_cpu_topology()
    from timm_tpu.perfbudget.replay import load_self_doc, run_replay, validate_self_result
    from timm_tpu.utils import configure_compile_cache

    configure_compile_cache()
    names = [s.strip() for s in args.replay_steps.split(',') if s.strip()] or None
    _status(f'replay: PERF.md checklist ({"dry-run" if args.dry_run else "LIVE"})')
    doc, rc = run_replay(dry_run=args.dry_run, self_path=SELF_RESULT_PATH,
                         names=names, log=lambda m: _status(m))
    errs = validate_self_result(load_self_doc(SELF_RESULT_PATH))
    statuses = ' '.join(f"{s['id']}={s['status']}" for s in doc['steps'])
    print(json.dumps({
        'metric': (f"replay ({'dry-run' if args.dry_run else 'live'}): "
                   f"{doc['completed']}/{doc['total']} ok, {doc['failed']} failed, "
                   f"{doc['skipped']} skipped -> {SELF_RESULT_PATH} [{statuses}]"
                   + (f'; SCHEMA ERRORS: {errs}' if errs else '')),
        'value': float(doc['completed']), 'unit': 'checklist steps ok',
        'vs_baseline': None}), flush=True)
    return rc if not errs else (rc or 2)


def _kernels_ab(args) -> int:
    """Kernel-portfolio win-or-delete A/B (PERF.md 'Kernel portfolio &
    win-or-delete harness'): every registered Pallas kernel runs its declared
    regime cases against its XLA reference — parity first (a kernel that is
    wrong gets 'delete' without being timed), then wall-clock on hardware the
    kernel actually claimed. The dry-run arm is the tier-1 CPU smoke: parity
    still gates, TPU-claimed kernels come back 'pending'. Verdict records
    stream into BENCH_SELF.json so the round file carries the decision data
    even when the driver keeps only the tail line."""
    _force_cpu_topology()
    from timm_tpu.perfbudget.replay import load_self_doc, save_self_doc
    from timm_tpu.utils import configure_compile_cache

    configure_compile_cache()
    from timm_tpu.kernels.harness import format_verdict_line, run_kernel_ab

    live = not args.dry_run
    _status(f'kernels: portfolio win-or-delete A/B ({"LIVE" if live else "dry-run"})')
    verdicts = run_kernel_ab(live=live, steps=max(1, min(args.steps, 20)))
    for rec in verdicts:
        print(format_verdict_line(rec), flush=True)
    doc = load_self_doc(SELF_RESULT_PATH)
    doc['kernels'] = {'at': time.strftime('%Y-%m-%dT%H:%M:%SZ', time.gmtime()),
                      'live': live, 'verdicts': verdicts}
    save_self_doc(SELF_RESULT_PATH, doc)
    counts = {v: sum(1 for r in verdicts if r['verdict'] == v)
              for v in ('keep', 'pending', 'delete')}
    print(json.dumps({
        'metric': (f"kernel portfolio A/B ({'live' if live else 'dry-run'}): "
                   f"{counts['keep']} keep, {counts['pending']} pending, "
                   f"{counts['delete']} delete of {len(verdicts)} registered "
                   f'-> {SELF_RESULT_PATH}'),
        'value': float(len(verdicts) - counts['delete']),
        'unit': 'kernels surviving', 'vs_baseline': None}), flush=True)
    return 0 if counts['delete'] == 0 else 2


def _analysis(args) -> int:
    """Static-analysis gate as a bench mode: the same suite the replay
    checklist's `analysis` step runs, callable standalone so a bench round
    can refuse to measure a repo the analyzers reject.
    --dry-run is the cheap arm (Tier A source rules + the zoo smoke subset,
    no probe lowering); full mode runs every rule, including the jaxpr/HLO
    passes over the freshly lowered probe programs. The per-rule report
    lands in BENCH_SELF.json next to the kernel verdicts."""
    _force_cpu_topology()
    from timm_tpu.perfbudget.replay import _run_analysis, load_self_doc, save_self_doc
    from timm_tpu.utils import configure_compile_cache

    configure_compile_cache()
    _status(f'analysis: static-analysis suite ({"dry-run" if args.dry_run else "full"})')
    spec = dict(tiers=('A',), zoo='smoke') if args.dry_run else {}
    result = _run_analysis(spec)
    doc = load_self_doc(SELF_RESULT_PATH)
    doc['analysis'] = dict(result, at=time.strftime('%Y-%m-%dT%H:%M:%SZ', time.gmtime()))
    save_self_doc(SELF_RESULT_PATH, doc)
    print(json.dumps({
        'metric': (f"static analysis ({'dry-run' if args.dry_run else 'full'}): "
                   f"{result['violations']} violation(s), {result['waived']} waived, "
                   f"{len(result['errors'])} analyzer error(s) -> {SELF_RESULT_PATH}"),
        'value': float(result['violations']), 'unit': 'violations',
        'vs_baseline': None}), flush=True)
    return result['exit_code']


def _compile_child(args) -> int:
    """One compile-cost measurement in a FRESH process (so 'cold' means cold):
    trace ms, lower+compile ms (hits the persistent disk cache when
    JAX_COMPILATION_CACHE_DIR points at a warm dir), total jaxpr equation
    count. A CPU analysis: the platform is chosen before the first JAX call."""
    os.environ['JAX_PLATFORMS'] = 'cpu'
    from timm_tpu.utils.compile_cache import configure_compile_cache, count_jaxpr_eqns
    cache_dir = configure_compile_cache()

    import jax
    import jax.numpy as jnp
    from flax import nnx

    import timm_tpu

    model = timm_tpu.create_model(args.model, img_size=args.img_size)
    if args.block_scan and hasattr(model, 'set_block_scan'):
        model.set_block_scan(True)
    model.eval()
    graphdef, state = nnx.split(model)
    x = jnp.zeros((2, args.img_size, args.img_size, 3), jnp.float32)

    def fwd(s, xx):
        return nnx.merge(graphdef, s)(xx)

    t0 = time.perf_counter()
    traced = jax.jit(fwd).trace(state, x)
    trace_ms = (time.perf_counter() - t0) * 1e3
    eqns = count_jaxpr_eqns(traced.jaxpr)
    t0 = time.perf_counter()
    traced.lower().compile()
    compile_ms = (time.perf_counter() - t0) * 1e3
    print(json.dumps({
        'metric': f'{args.model} fwd compile cost (scan={"on" if args.block_scan else "off"}, '
                  f'cache={cache_dir})',
        'value': round(trace_ms + compile_ms, 1), 'unit': 'ms', 'vs_baseline': None,
        'trace_ms': round(trace_ms, 1), 'compile_ms': round(compile_ms, 1),
        'jaxpr_eqns': eqns}), flush=True)
    return 0


def _run_compile_child(args, block_scan: bool, cache_dir: str):
    """Spawn a fresh-process _compile_child run and parse its result line."""
    cmd = [sys.executable, os.path.abspath(__file__), '--compile-child',
           '--model', args.model, '--img-size', str(args.img_size)]
    if block_scan:
        cmd += ['--block-scan']
    env = dict(os.environ, JAX_PLATFORMS='cpu', JAX_COMPILATION_CACHE_DIR=cache_dir)
    try:
        r = subprocess.run(cmd, env=env, capture_output=True, text=True, timeout=900)
    except subprocess.TimeoutExpired:
        return None
    for line in reversed((r.stdout or '').strip().splitlines()):
        try:
            d = json.loads(line)
            if isinstance(d, dict) and 'trace_ms' in d:
                return d
        except Exception:
            continue
    tail = '\n'.join((r.stderr or '').strip().splitlines()[-10:])
    print(f'compile child rc={r.returncode}, no result; stderr tail:\n{tail}',
          file=sys.stderr, flush=True)
    return None


def _compile_report(args) -> int:
    """Compile & input-pipeline cost report (PERF.md 'compile & input
    pipeline'): for scan off/on, run a COLD child (fresh process, empty disk
    cache) and a WARM child (fresh process, same disk cache) and report
    cold-trace / cold-compile / warm-compile ms + jaxpr equation counts. CPU
    only: the children run with JAX_PLATFORMS=cpu and this parent never
    imports JAX, so no process here takes a chip."""
    import shutil

    _status('compile-report: 4 fresh-process measurements (scan off/on x cold/warm)')
    rows = {}
    for scan in (False, True):
        cache_dir = os.path.join(_REPO_ROOT, 'output', 'bench', 'compile_report',
                                 'scan_on' if scan else 'scan_off')
        shutil.rmtree(cache_dir, ignore_errors=True)  # 'cold' means an empty cache
        try:
            for run in ('cold', 'warm'):
                r = _run_compile_child(args, scan, cache_dir)
                if r is None:
                    print(json.dumps({
                        'metric': f'compile-report FAILED at scan={scan} {run}',
                        'value': 0.0, 'unit': 'x', 'vs_baseline': None}), flush=True)
                    return 2
                rows[(scan, run)] = r
                _status(f'compile-report: scan={"on" if scan else "off"} {run}: '
                        f'trace {r["trace_ms"]}ms compile {r["compile_ms"]}ms '
                        f'eqns {r["jaxpr_eqns"]}')
        finally:
            shutil.rmtree(cache_dir, ignore_errors=True)

    def total(scan, run):
        r = rows[(scan, run)]
        return r['trace_ms'] + r['compile_ms']

    scan_speedup = total(False, 'cold') / max(total(True, 'cold'), 1e-9)
    warm_ratio = rows[(True, 'warm')]['compile_ms'] / max(rows[(True, 'cold')]['compile_ms'], 1e-9)
    eqn_ratio = rows[(False, 'cold')]['jaxpr_eqns'] / max(rows[(True, 'cold')]['jaxpr_eqns'], 1)
    print(json.dumps({
        'metric': (f'{args.model} compile report: cold trace+compile '
                   f'{total(False, "cold"):.0f}ms (loop) -> {total(True, "cold"):.0f}ms (scan) '
                   f'= {scan_speedup:.1f}x; warm disk-cache compile '
                   f'{rows[(True, "warm")]["compile_ms"]:.0f}ms vs cold '
                   f'{rows[(True, "cold")]["compile_ms"]:.0f}ms; jaxpr eqns '
                   f'{rows[(False, "cold")]["jaxpr_eqns"]} (loop) vs '
                   f'{rows[(True, "cold")]["jaxpr_eqns"]} (scan, {eqn_ratio:.1f}x fewer)'),
        'value': round(scan_speedup, 2), 'unit': 'x cold trace+compile (scan vs loop)',
        'vs_baseline': None,
        'detail': {f'{"scan" if s else "loop"}_{r}': rows[(s, r)]
                   for s in (False, True) for r in ('cold', 'warm')},
        'warm_vs_cold_compile': round(warm_ratio, 3)}), flush=True)
    return 0


def _measure(args) -> int:
    """The device measurement, in this process. No TPU, or a device kind with
    no known peak: exit code 2 and no number."""
    import jax
    device = jax.devices()[0]
    if device.platform != 'tpu':
        print(f'bench: no accelerator (platform {device.platform!r}); this '
              'benchmark measures on a TPU only and has no fallback',
              file=sys.stderr, flush=True)
        return 2
    peak = _chip_peak(device.device_kind)
    import jax.numpy as jnp
    import numpy as np
    import optax
    from flax import nnx

    import timm_tpu
    from timm_tpu.loss import cross_entropy
    from timm_tpu.optim import create_optimizer_v2
    from timm_tpu.parallel import (
        build_opt_shardings, build_param_shardings, create_mesh, data_sharding,
        replicate_sharding, set_global_mesh,
    )
    from timm_tpu.utils import configure_compile_cache

    configure_compile_cache()

    mesh = create_mesh(fsdp=args.fsdp if args.fsdp else None,
                       tp=args.tp if args.tp else None)
    set_global_mesh(mesh)
    n_chips = mesh.size
    # bs128/chip benched fastest for ViT-B train on v5e with the einsum
    # attention path (867 img/s vs 786 w/ XLA dot_product_attention, 758 @64)
    batch_size = args.batch_size or ((128 if args.bench == 'train' else 256) * n_chips)
    K = args.steps

    model_kwargs, opt_kwargs, knob_tag = _apply_precision_knobs(args)
    kwargs = dict(model_kwargs)
    if args.img_size != 224:
        kwargs['img_size'] = args.img_size
    model = timm_tpu.create_model(args.model, dtype=jnp.bfloat16, **kwargs)
    if args.block_scan and hasattr(model, 'set_block_scan'):
        model.set_block_scan(True)
        knob_tag += ' [block_scan]'

    rng = np.random.RandomState(0)
    x = jax.device_put(
        jnp.asarray(rng.rand(batch_size, args.img_size, args.img_size, 3), jnp.bfloat16),
        data_sharding(mesh, 4))
    t = jax.device_put(jnp.asarray(rng.randint(0, model.num_classes, batch_size)),
                       data_sharding(mesh, 1))

    aug_fn = aug_raw = None
    if args.device_augment:
        # on-device data path A/B: the batch stays raw uint8 + host-sampled
        # params, and the augment program runs fused inside the scanned step
        # so its per-step cost rides the measurement
        import functools

        from timm_tpu.data.device_augment import augment_image_batch
        s = args.img_size
        aug_raw = {
            'image': jax.device_put(jnp.asarray(
                rng.randint(0, 256, (batch_size, s, s, 3)).astype(np.uint8)),
                data_sharding(mesh, 4)),
            'target': t,
            'lam': jax.device_put(jnp.asarray(rng.beta(0.8, 0.8, batch_size), jnp.float32),
                                  data_sharding(mesh, 1)),
            'use_cutmix': jax.device_put(jnp.zeros((batch_size,), bool),
                                         data_sharding(mesh, 1)),
            'bbox': jax.device_put(jnp.zeros((batch_size, 4), jnp.int32),
                                   data_sharding(mesh, 2)),
        }
        aug_fn = functools.partial(
            augment_image_batch, mean=(0.5,) * 3, std=(0.5,) * 3,
            num_classes=model.num_classes, smoothing=0.1, out_dtype=jnp.bfloat16)
        knob_tag += ' [device_augment]'

    if args.bench == 'train':
        model.train()
        opt = create_optimizer_v2(model, opt='adamw', lr=1e-3, weight_decay=0.05, **opt_kwargs)
        graphdef, params, rest = nnx.split(model, nnx.Param, ...)
        # FSDP placement: large weights + their m/v shard over the 'fsdp'
        # axis (replicated-everything when the mesh has no such axis)
        param_sh = build_param_shardings(params, mesh)
        opt_sh, _ = build_opt_shardings(opt, params, mesh)
        params = jax.device_put(params, param_sh)
        # abstract on-mesh init: replicated m/v never materialize
        opt_state = jax.jit(opt.init, out_shardings=opt_sh)(params)  # no-donate: init

        # donation + returning the updated state lets XLA alias the params and
        # AdamW buffers in place (input-output aliasing): ~1 GB less HBM copy
        # traffic per fused K-step call for ViT-B. --no-donate A/Bs it off.
        donate = () if args.no_donate else (0, 1)

        def multi_step(params, opt_state, x, t):
            def body(carry, _):
                params, opt_state = carry

                def loss_fn(p):
                    m = nnx.merge(graphdef, p, rest, copy=True)
                    if aug_fn is not None:
                        xf, y = aug_fn(x)  # x is the raw uint8 batch dict
                        return -(y * jax.nn.log_softmax(
                            m(xf).astype(jnp.float32))).sum(-1).mean()
                    return cross_entropy(m(x), t)
                loss, grads = jax.value_and_grad(loss_fn)(params)
                updates, opt_state = opt.update(grads, opt_state, params, lr=1e-3)
                params = optax.apply_updates(params, updates)
                return (params, opt_state), loss
            (params, opt_state), losses = jax.lax.scan(body, (params, opt_state), None, length=K)
            return params, opt_state, losses[-1]

        if aug_fn is not None:
            x = aug_raw  # the augment program consumes the whole param'd batch
            x_sh = {'image': data_sharding(mesh, 4), 'target': data_sharding(mesh, 1),
                    'lam': data_sharding(mesh, 1), 'use_cutmix': data_sharding(mesh, 1),
                    'bbox': data_sharding(mesh, 2)}
        else:
            x_sh = data_sharding(mesh, 4)
        multi_step = jax.jit(
            multi_step, donate_argnums=donate,
            in_shardings=(param_sh, opt_sh, x_sh, data_sharding(mesh, 1)),
            out_shardings=(param_sh, opt_sh, replicate_sharding(mesh)))

        # warm-up compiles + runs once; its returned state feeds the timed
        # call (donation invalidates the inputs, and chaining state is the
        # realistic steady-state pattern)
        params, opt_state, out = multi_step(params, opt_state, x, t)
        float(out)
        t0 = time.perf_counter()
        params, opt_state, out = multi_step(params, opt_state, x, t)
        float(out)
        dt = time.perf_counter() - t0
        flops_mult = 3.0  # fwd + bwd
    else:
        model.eval()
        graphdef, state = nnx.split(model)
        if args.quantize:
            # serve-path A/B: the program's weight inputs become the int8
            # qvalues + scales; dequant runs at use inside every scanned
            # forward, so HBM holds (and streams) the ~0.27x footprint
            from timm_tpu.quantize import dequantize_tree, quantize_tree
            state = quantize_tree(state)
            knob_tag += ' [quant=int8]'

        @jax.jit
        def multi_fwd(state, x):
            def body(carry, _):
                m_state = dequantize_tree(state) if args.quantize else state
                out = nnx.merge(graphdef, m_state)(x + carry * 0)
                return out.mean().astype(jnp.bfloat16), ()
            final, _ = jax.lax.scan(body, jnp.zeros((), jnp.bfloat16), None, length=K)
            return final

        float(multi_fwd(state, x))
        t0 = time.perf_counter()
        float(multi_fwd(state, x))
        dt = time.perf_counter() - t0
        flops_mult = 1.0

    per_step = dt / K
    img_per_sec_chip = batch_size / per_step / n_chips

    # MFU from compiled forward cost
    graphdef_e, state_e = nnx.split(model)
    x_e = x['image'].astype(jnp.bfloat16) / 255 if isinstance(x, dict) else x
    fwd_flops = jax.jit(lambda s, xx: nnx.merge(graphdef_e, s)(xx)).lower(
        state_e, x_e).compile().cost_analysis()['flops']
    mfu = (fwd_flops * flops_mult / n_chips) / per_step / peak

    baseline = BASELINES.get((args.model, args.bench))
    # mesh shape + donation state make BENCH_*.json rows attributable to the
    # sharding/donation configuration that produced them
    mesh_tag = 'x'.join(str(mesh.shape[a]) for a in mesh.axis_names) + f'({",".join(mesh.axis_names)})'
    knob_tag += f' [mesh={mesh_tag}, donate={"off" if args.no_donate else "on"}]'
    metric = (f'{args.model} {args.bench} img/s/chip (bf16, bs{batch_size}, {n_chips} chip, '
              f'{device.device_kind}){knob_tag}, MFU={mfu:.2f}')
    result = {
        'metric': metric,
        'value': round(img_per_sec_chip, 1),
        'unit': 'img/s/chip',
        'vs_baseline': round(img_per_sec_chip / baseline, 3) if baseline else None,
    }
    print(json.dumps(result), flush=True)
    if args.save_self:
        from timm_tpu.perfbudget.replay import record_result
        record_result(SELF_RESULT_PATH, result)
    return 0


if __name__ == '__main__':
    main()
