"""Lower real programs, extract hardware-independent perf metrics.

One :class:`ProbeConfig` = one budgeted entry in perf_budgets.json. The probe
builds the REAL `TrainingTask` jitted step (or the real serve engine bucket
programs) on a {model x fsdp x tp x block_scan x grad_accum} point and
extracts everything XLA will tell us without a TPU:

  * ``trace_ms`` / ``jaxpr_eqns``  — trace cost and equation count of the
    closed jaxpr (the O(1)-in-depth / O(1)-in-accum contracts);
  * ``flops`` / ``bytes_accessed`` — `compiled.cost_analysis()` of the AOT-
    compiled step (XLA's own per-execution estimate; deterministic);
  * ``param_bytes_*`` / ``opt_bytes_per_device`` / ``activation_bytes_*`` —
    per-device state footprint via the parallel/sharding.py calculators and
    the actual on-device shard sizes;
  * ``donation_aliases`` / ``donation_ok`` — the compiled HLO header's
    ``input_output_alias`` table: donated state buffers must actually alias
    (the train step's donation is usable — params/opt/EMA outputs match their
    inputs — so a missing table means donation silently died);
  * ``no_replicated_residual``     — the tp forward HLO carries the
    per-device residual shape and never materializes the full one
    (involuntary-remat regression gate, mirrors test_sharding);
  * ``serve_programs`` / ``serve_donation_declared`` — every declared bucket
    has an AOT executable and its input donation provably reached lowering
    (`InferenceEngine.donation_report`).

Collect modes trim tier-1 cost: ``trace`` never compiles, ``full`` compiles
the train step, ``fwd`` compiles a forward-only program (the tp residual
check — same program test_sharding compiles, so the persistent cache is
shared), ``serve`` drives the engine prewarm path, ``augment`` compiles the
on-device data-path programs (fused image augment + donated naflex augment),
``naflex`` compiles the packed variable-resolution train step at one bucket
shape.
"""
from __future__ import annotations

import contextlib
import dataclasses
import logging
import time
from typing import Dict, List, Optional, Sequence, Tuple

_logger = logging.getLogger(__name__)

__all__ = ['ProbeConfig', 'DEFAULT_MATRIX', 'probe_config', 'run_matrix',
           'donation_evidence', 'capture_programs']


@dataclasses.dataclass(frozen=True)
class ProbeConfig:
    name: str
    model: str = 'test_vit'
    model_kwargs: Tuple[Tuple[str, object], ...] = ()
    batch_size: int = 8
    fsdp: int = 1
    tp: int = 1
    block_scan: Optional[bool] = None     # None = model default
    grad_accum: int = 1
    opt: str = 'adamw'
    collect: str = 'full'   # 'trace' | 'full' | 'fwd' | 'serve' | 'quant' | 'augment' | 'naflex' | 'elastic'
    buckets: Tuple[int, ...] = (2, 4)     # serve only
    seq_len: int = 25                     # naflex packed probe only
    # batch spatial size when it is NOT a ctor kwarg (conv models size from
    # the data; their ctors reject img_size) — falls back to model_kwargs
    img_size: Optional[int] = None
    # tp 'fwd' residual-shape gate (config-specific HLO shape strings)
    fwd_expect_shard: str = ''
    fwd_forbid_full: str = ''

    def kwargs(self) -> Dict:
        return dict(self.model_kwargs)

    def img(self, default: int = 224) -> int:
        return int(self.img_size or self.kwargs().get('img_size', default))


# The tier-1 matrix: one config per proven perf property, trimmed so the
# whole suite stays within its <=60s warm budget (trace-only where a compile
# adds nothing; compiles ride the persistent disk cache).
DEFAULT_MATRIX: Tuple[ProbeConfig, ...] = (
    # the canonical data-mesh step: FLOPs/bytes/donation baseline
    ProbeConfig(name='base', model='test_vit',
                model_kwargs=(('num_classes', 10), ('img_size', 32)),
                batch_size=8, collect='full'),
    # depth-12 scanned stack: the block-scan O(1)-in-depth contract; the
    # injected-regression test re-probes this with block_scan=False
    ProbeConfig(name='scan_depth12', model='vit_tiny_patch16_224',
                model_kwargs=(('img_size', 64),),
                batch_size=8, block_scan=True, collect='full'),
    # fsdp=4: sharded param/opt bytes + donation must stay aliased
    ProbeConfig(name='fsdp4', model='test_vit',
                model_kwargs=(('num_classes', 10), ('img_size', 32)),
                batch_size=8, fsdp=4, collect='full'),
    # fsdp x tp = (2,2): residual stays sharded inside the scanned body
    # (same forward program test_sharding compiles — disk cache shared)
    ProbeConfig(name='tp22', model='vit_tiny_patch16_224',
                model_kwargs=(('img_size', 64),),
                batch_size=8, fsdp=2, tp=2, block_scan=True, collect='fwd',
                fwd_expect_shard='f32[2,17,96]', fwd_forbid_full='f32[8,17,192]'),
    # scanned grad accumulation: trace cost O(1) in accum steps (trace-only)
    ProbeConfig(name='accum4', model='test_vit',
                model_kwargs=(('num_classes', 10), ('img_size', 32)),
                batch_size=8, grad_accum=4, collect='trace'),
    # serve engine: every bucket AOT-compiled, input donation reaches lowering
    ProbeConfig(name='serve_test_vit', model='test_vit',
                model_kwargs=(('num_classes', 10), ('img_size', 32)),
                collect='serve', buckets=(2, 4)),
    # int8 serve path: quantized program bytes-accessed + per-device param
    # bytes at <=0.55x fp32, donation declared, scale sharding legal on
    # (fsdp=2, tp=2) — the ROADMAP-3a claim, provable without hardware
    ProbeConfig(name='quant_serve_int8', model='test_vit',
                model_kwargs=(('num_classes', 10), ('img_size', 32)),
                collect='quant', buckets=(2, 4)),
    # on-device augment programs: the fused uint8->erase->mixup->normalize
    # image program stays tiny (eqns/flops/bytes), and the naflex variant's
    # f32 patches donation provably reaches lowering (must-alias in the HLO)
    ProbeConfig(name='device_augment',
                model_kwargs=(('num_classes', 10), ('img_size', 32)),
                batch_size=8, collect='augment'),
    # NaFlex packed train step: dict-batch program the bucket ladder reuses
    # per seq_len — eqn/FLOP/donation baseline for one bucket shape
    ProbeConfig(name='naflex_packed', model='test_naflexvit',
                model_kwargs=(('num_classes', 10),),
                batch_size=8, collect='naflex', seq_len=25),
    # elastic resize: state saved on an 8-device (2,4) mesh re-places on the
    # 4-device post-resize mesh (fsdp clamped by resolve_elastic_axes), the
    # rescale solver holds the global batch, and the RE-PLACED train step
    # still lowers with donation intact (resilience/elastic.py)
    ProbeConfig(name='elastic_resize', model='test_vit',
                model_kwargs=(('num_classes', 10), ('img_size', 32)),
                batch_size=8, fsdp=4, collect='elastic'),
    # hierarchical stage scan (ISSUE-20): the conv family baseline — convnext
    # sizes from the data (ctor takes no img_size; the new img_size field
    # sizes the batch), stages scanned via the set_block_scan alias
    ProbeConfig(name='stage_scan_convnext', model='test_convnext',
                model_kwargs=(('num_classes', 10),), img_size=64,
                batch_size=8, block_scan=True, collect='full'),
    # ...and the windowed-attention baseline at swin's native test size
    # (relative-position tables are resolution-bound)
    ProbeConfig(name='stage_scan_swin', model='test_swin',
                model_kwargs=(('num_classes', 10),), img_size=96,
                batch_size=8, block_scan=True, collect='full'),
)


# ---- program capture (timm_tpu.analysis Tier B/C hook) ----------------------
#
# The probes are the one place the repo lowers its REAL programs; the
# analysis suite's jaxpr/HLO passes audit those exact artifacts instead of
# re-lowering. Inside `capture_programs()`, every probe records the jaxprs
# and compiled executables it produces, tagged with the invariant each one
# is expected to uphold (donation via alias table vs declared-at-lowering,
# residual-sharding shape strings).

_CAPTURE: Optional[List[Dict]] = None


@contextlib.contextmanager
def capture_programs():
    """Collect {'config','name','kind','jaxpr','compiled','expect'} records
    for every program the probes lower while the context is active."""
    global _CAPTURE
    prev, _CAPTURE = _CAPTURE, []
    try:
        yield _CAPTURE
    finally:
        _CAPTURE = prev


def _capture(config: str, name: str, kind: str, *,
             jaxpr=None, compiled=None, **expect) -> None:
    if _CAPTURE is not None:
        _CAPTURE.append(dict(config=config, name=name, kind=kind,
                             jaxpr=jaxpr, compiled=compiled, expect=expect))


# configs whose cost_analysis() already raised once this process — the
# warning fires once per config, not once per retry/rerank.
_COST_WARNED: set = set()


def _cost_analysis(compiled, name: str = '') -> Dict[str, float]:
    """Normalize `compiled.cost_analysis()` across jax versions (dict or
    [dict]); returns {} when the backend reports nothing. A raising backend
    is logged once per config name — an autotune/budget consumer ranking on
    partially-missing costs must be able to see WHY in the log."""
    try:
        ca = compiled.cost_analysis()
    except Exception as e:
        if name not in _COST_WARNED:
            _COST_WARNED.add(name)
            _logger.warning(
                'perfbudget: cost_analysis() raised for config %r '
                '(%s: %s) — flops/bytes_accessed will be missing',
                name or '<unnamed>', type(e).__name__, e)
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca) if ca else {}


def donation_evidence(compiled) -> Dict[str, object]:
    """Alias evidence from a compiled executable's HLO header: number of
    `may-alias`/`must-alias` entries in its ``input_output_alias`` table."""
    header = compiled.as_text().splitlines()[0] if hasattr(compiled, 'as_text') else ''
    aliases = (header.count('may-alias') + header.count('must-alias')
               if 'input_output_alias' in header else 0)
    return {'aliases': int(aliases), 'header': header}


def _device_state_bytes(tree) -> int:
    """Exact per-device bytes of a placed pytree: one addressable shard per
    leaf (correct for both replicated and sharded placements)."""
    import jax
    total = 0
    for leaf in jax.tree.leaves(tree):
        shards = getattr(leaf, 'addressable_shards', None)
        if shards:
            total += int(shards[0].data.nbytes)
    return total


def _model_dims(model) -> Optional[Tuple[int, int, int]]:
    """(seq_len, width, depth) for the activation calculator, read off the
    live model; None for models without a pos_embed/blocks ViT shape."""
    pos = getattr(model, 'pos_embed', None)
    blocks = getattr(model, 'blocks', None)
    if pos is None or blocks is None:
        return None
    shape = getattr(pos, 'shape', None)
    if not shape or len(shape) != 3:
        return None
    try:
        depth = len(blocks)
    except TypeError:
        return None
    return int(shape[1]), int(shape[2]), int(depth)


def _probe_train(cfg: ProbeConfig) -> Dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax import nnx

    import timm_tpu
    from ..loss import LabelSmoothingCrossEntropy
    from ..optim import create_optimizer_v2
    from ..parallel import (
        activation_bytes_per_device, build_param_shardings, create_mesh,
        param_bytes_per_device, set_global_mesh, shard_batch,
    )
    from ..task import ClassificationTask
    from ..utils.compile_cache import count_jaxpr_eqns

    mesh = create_mesh(fsdp=cfg.fsdp, tp=cfg.tp)
    # the models' activation sharding constraints resolve against the GLOBAL
    # mesh at trace time — it must match the probe mesh or GSPMD degenerates
    # into the involuntary-remat regime this probe exists to detect
    set_global_mesh(mesh)
    model = timm_tpu.create_model(cfg.model, **cfg.kwargs())
    if cfg.block_scan is not None and hasattr(model, 'set_block_scan'):
        model.set_block_scan(cfg.block_scan)
    dims = _model_dims(model)

    rng = np.random.RandomState(0)
    s = cfg.img(224)
    num_classes = int(cfg.kwargs().get('num_classes', 1000))
    batch = {'input': jnp.asarray(rng.rand(cfg.batch_size, s, s, 3), jnp.float32),
             'target': jnp.asarray(rng.randint(0, num_classes, cfg.batch_size))}

    metrics: Dict = {}
    if cfg.collect == 'fwd':
        # forward-only program (the tp residual-sharding gate): mirrors
        # test_tp_constraint_in_scan_body_and_no_involuntary_remat
        model.eval()
        graphdef, state = nnx.split(model)
        state = jax.device_put(state, build_param_shardings(state, mesh))

        def fwd(state, x):
            return nnx.merge(graphdef, state)(x)

        x = shard_batch(batch['input'], mesh)
        t0 = time.perf_counter()
        closed = jax.make_jaxpr(fwd)(state, x)
        metrics['trace_ms'] = round((time.perf_counter() - t0) * 1e3, 3)
        metrics['jaxpr_eqns'] = count_jaxpr_eqns(closed)
        compiled = jax.jit(fwd).lower(state, x).compile()
        ca = _cost_analysis(compiled, cfg.name)
        if 'flops' in ca:
            metrics['flops'] = float(ca['flops'])
        if 'bytes accessed' in ca:
            metrics['bytes_accessed'] = float(ca['bytes accessed'])
        _capture(cfg.name, f'{cfg.name}/fwd', 'fwd',
                 jaxpr=closed, compiled=compiled,
                 expect_shard=cfg.fwd_expect_shard or None,
                 forbid_full=cfg.fwd_forbid_full or None)
        if cfg.fwd_expect_shard:
            hlo = compiled.as_text()
            metrics['no_replicated_residual'] = bool(
                cfg.fwd_expect_shard in hlo
                and (not cfg.fwd_forbid_full or cfg.fwd_forbid_full not in hlo))
        rep, shard = param_bytes_per_device(nnx.state(model, nnx.Param), mesh)
        metrics['param_bytes_replicated'] = int(rep)
        metrics['param_bytes_sharded'] = int(shard)
        return metrics

    def build_task():
        return ClassificationTask(model,
                                  optimizer=create_optimizer_v2(model, opt=cfg.opt, lr=0.1),
                                  mesh=mesh, grad_accum_steps=cfg.grad_accum,
                                  train_loss_fn=LabelSmoothingCrossEntropy(0.1))

    task = build_task()
    batch = shard_batch(batch, mesh)

    # trace_ms = min over two FRESH tasks (a task's jit caches its first
    # trace, so re-timing needs a new step fn). Load spikes only ever inflate
    # a trace measurement, so the min tracks the true cost closely (~±5% here
    # vs ±15% single-shot) — tight enough that the 1.3x upper tolerance
    # separates block_scan=False (~1.45x) from noise without flaking tier-1.
    trace_times = []
    for t in (task, build_task()):
        t0 = time.perf_counter()
        jaxpr = t.trace_train_step(batch, lr=0.1)
        trace_times.append((time.perf_counter() - t0) * 1e3)
    metrics['trace_ms'] = round(min(trace_times), 3)
    metrics['jaxpr_eqns'] = count_jaxpr_eqns(jaxpr)

    params = nnx.state(task.model, nnx.Param)
    rep, shard = param_bytes_per_device(params, mesh, task.partition_rules)
    metrics['param_bytes_replicated'] = int(rep)
    metrics['param_bytes_sharded'] = int(shard)
    metrics['opt_bytes_per_device'] = _device_state_bytes(task.opt_state)
    if dims is not None:
        seq_len, width, depth = dims
        unc, con = activation_bytes_per_device(
            mesh, batch_size=cfg.batch_size, seq_len=seq_len, width=width, depth=depth)
        metrics['activation_bytes_unconstrained'] = int(unc)
        metrics['activation_bytes_constrained'] = int(con)

    if cfg.collect == 'full':
        compiled = task.lower_train_step(batch, lr=0.1)
        ca = _cost_analysis(compiled, cfg.name)
        if 'flops' in ca:
            metrics['flops'] = float(ca['flops'])
        if 'bytes accessed' in ca:
            metrics['bytes_accessed'] = float(ca['bytes accessed'])
        ev = donation_evidence(compiled)
        metrics['donation_aliases'] = ev['aliases']
        # the train step's donation is always usable (state outputs match
        # their donated inputs leaf-for-leaf): zero aliases = donation died
        metrics['donation_ok'] = ev['aliases'] > 0
        _capture(cfg.name, f'{cfg.name}/train_step', 'train_step',
                 jaxpr=jaxpr, compiled=compiled, donation='alias')
    else:
        _capture(cfg.name, f'{cfg.name}/train_step', 'train_step',
                 jaxpr=jaxpr)
    return metrics


def _probe_augment(cfg: ProbeConfig) -> Dict:
    """The on-device data-path programs (data/device_augment.py). Two pieces
    of evidence: the fused image program (uint8 -> erase -> mixup -> normalize
    -> soft targets) stays a small fixed-size jaxpr with bytes dominated by
    the batch itself, and the NaFlex variant's float32 patches buffer donation
    survives to the compiled HLO as a real alias (the uint8 image input can
    never alias its float output, so the naflex program is where donation is
    provable)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..data.device_augment import augment_image_batch, augment_naflex_batch
    from ..parallel import create_mesh, set_global_mesh, shard_batch
    from ..utils.compile_cache import count_jaxpr_eqns

    mesh = create_mesh(fsdp=cfg.fsdp, tp=cfg.tp)
    set_global_mesh(mesh)
    rng = np.random.RandomState(0)
    B = cfg.batch_size
    s = cfg.img(32)
    num_classes = int(cfg.kwargs().get('num_classes', 10))
    raw = shard_batch({
        'image': jnp.asarray(rng.randint(0, 256, (B, s, s, 3)), jnp.uint8),
        'target': jnp.asarray(rng.randint(0, num_classes, B)),
        'lam': jnp.asarray(rng.beta(0.8, 0.8, B), jnp.float32),
        'use_cutmix': jnp.zeros((B,), bool),
        'bbox': jnp.zeros((B, 4), jnp.int32),
        'erase_box': jnp.zeros((B, 1, 4), jnp.int32),
    }, mesh)
    fn = functools.partial(augment_image_batch, mean=(0.5,) * 3, std=(0.5,) * 3,
                           num_classes=num_classes, smoothing=0.1)

    metrics: Dict = {}
    t0 = time.perf_counter()
    closed = jax.make_jaxpr(fn)(raw)
    metrics['trace_ms'] = round((time.perf_counter() - t0) * 1e3, 3)
    metrics['jaxpr_eqns'] = count_jaxpr_eqns(closed)
    compiled = jax.jit(fn).lower(raw).compile()
    ca = _cost_analysis(compiled, cfg.name)
    if 'flops' in ca:
        metrics['flops'] = float(ca['flops'])
    if 'bytes accessed' in ca:
        metrics['bytes_accessed'] = float(ca['bytes accessed'])

    L, pd = 25, 4 * 4 * 3
    nf = shard_batch({
        'patches': jnp.asarray(rng.rand(B, L, pd), jnp.float32),
        'patch_coord': jnp.asarray(rng.randint(0, 5, (B, L, 2)), jnp.int32),
        'patch_valid': jnp.ones((B, L), bool),
        'target': jnp.asarray(rng.randint(0, num_classes, B)),
        'erase_mask': jnp.zeros((B, L), bool),
    }, mesh)
    _capture(cfg.name, f'{cfg.name}/image_augment', 'augment',
             jaxpr=closed, compiled=compiled)
    nf_fn = functools.partial(augment_naflex_batch, mean=(0.5,) * 3, std=(0.5,) * 3)
    nf_compiled = jax.jit(nf_fn, donate_argnums=(0,)).lower(nf).compile()
    _capture(cfg.name, f'{cfg.name}/naflex_augment', 'augment',
             compiled=nf_compiled, donation='alias')
    ev = donation_evidence(nf_compiled)
    metrics['naflex_donation_aliases'] = ev['aliases']
    # the (B, L, D) float patches round-trip f32 -> f32 at unchanged shape:
    # the donation MUST alias; zero aliases means it silently died
    metrics['naflex_donation_ok'] = ev['aliases'] > 0
    return metrics


def _probe_naflex(cfg: ProbeConfig) -> Dict:
    """The packed variable-resolution train step (NaFlexClassificationTask on
    a {patches, patch_coord, patch_valid, target} dict batch) at one bucket
    shape: trace/eqn cost, XLA flops/bytes, and state donation — the program
    every bucket in the seq-len ladder re-instantiates per shape."""
    import jax.numpy as jnp
    import numpy as np
    from flax import nnx

    import timm_tpu
    from ..optim import create_optimizer_v2
    from ..parallel import (
        create_mesh, param_bytes_per_device, set_global_mesh, shard_batch,
    )
    from ..task import NaFlexClassificationTask
    from ..utils.compile_cache import count_jaxpr_eqns

    mesh = create_mesh(fsdp=cfg.fsdp, tp=cfg.tp)
    set_global_mesh(mesh)
    model = timm_tpu.create_model(cfg.model, **cfg.kwargs())
    model.train()
    p = getattr(model.embeds, 'patch_size', 16)
    num_classes = int(cfg.kwargs().get('num_classes', 1000))

    rng = np.random.RandomState(0)
    B, L = cfg.batch_size, cfg.seq_len
    batch = shard_batch({
        'patches': jnp.asarray(rng.rand(B, L, p * p * 3), jnp.float32),
        'patch_coord': jnp.asarray(rng.randint(0, 5, (B, L, 2)), jnp.int32),
        'patch_valid': jnp.asarray(np.arange(L)[None, :]
                                   < rng.randint(L // 2, L + 1, (B, 1))),
        'target': jnp.asarray(rng.randint(0, num_classes, B)),
    }, mesh)

    def build_task():
        return NaFlexClassificationTask(
            model, optimizer=create_optimizer_v2(model, opt=cfg.opt, lr=0.1),
            mesh=mesh, grad_accum_steps=cfg.grad_accum)

    task = build_task()
    metrics: Dict = {}
    trace_times = []
    for t in (task, build_task()):
        t0 = time.perf_counter()
        jaxpr = t.trace_train_step(batch, lr=0.1)
        trace_times.append((time.perf_counter() - t0) * 1e3)
    metrics['trace_ms'] = round(min(trace_times), 3)
    metrics['jaxpr_eqns'] = count_jaxpr_eqns(jaxpr)

    rep, shard = param_bytes_per_device(
        nnx.state(task.model, nnx.Param), mesh, task.partition_rules)
    metrics['param_bytes_replicated'] = int(rep)
    metrics['param_bytes_sharded'] = int(shard)

    compiled = task.lower_train_step(batch, lr=0.1)
    ca = _cost_analysis(compiled, cfg.name)
    if 'flops' in ca:
        metrics['flops'] = float(ca['flops'])
    if 'bytes accessed' in ca:
        metrics['bytes_accessed'] = float(ca['bytes accessed'])
    ev = donation_evidence(compiled)
    metrics['donation_aliases'] = ev['aliases']
    metrics['donation_ok'] = ev['aliases'] > 0
    _capture(cfg.name, f'{cfg.name}/train_step', 'train_step',
             jaxpr=jaxpr, compiled=compiled, donation='alias')
    return metrics


def _probe_serve(cfg: ProbeConfig) -> Dict:
    from ..serve import InferenceEngine

    eng = InferenceEngine(buckets=cfg.buckets)
    eng.add_model(cfg.model, **cfg.kwargs())
    exes = eng.aot_executables(cfg.model)
    metrics: Dict = {
        'serve_programs': set(exes) == set(cfg.buckets),
    }
    flops = 0.0
    have_flops = False
    for bucket in sorted(exes):
        ca = _cost_analysis(exes[bucket], f'{cfg.name}/bucket{bucket}')
        if 'flops' in ca:
            flops += float(ca['flops'])
            have_flops = True
    if have_flops:
        metrics['flops'] = flops
    report = eng.donation_report(cfg.model)
    metrics['serve_donation_declared'] = bool(report) and all(
        r['declared'] for r in report.values())
    for bucket in sorted(exes):
        _capture(cfg.name, f'{cfg.name}/bucket{bucket}', 'serve_bucket',
                 compiled=exes[bucket], donation='declared',
                 declared=bool(report.get(bucket, {}).get('declared')))
    return metrics


def _probe_quant(cfg: ProbeConfig) -> Dict:
    """Int8 serve-path budgets: the quantized serve program vs its fp32 twin.

    The acceptance claim is hardware-independent — per-device param bytes AND
    the compiled program's HBM bytes-accessed must land at <= 0.55x the fp32
    baseline (``quant_halves_hbm``), with the input-batch donation still
    declared on every bucket program and the int8 pytree placeable under a
    real (fsdp=2, tp=2) mesh where every scale rides its kernel's spec
    (``quant_sharding_ok``).

    Two bytes-accessed measures are reported because they answer different
    questions:

      * ``bytes_accessed*`` — XLA's aggregate ``cost_analysis()`` estimate.
        Informative only: the pre-fusion cost model charges the dequantized
        fp32 weights as a materialized intermediate, so this aggregate does
        NOT drop under int8 even though on real hardware the dequant is a
        fusion transient (cache/VMEM resident, never HBM round-trip traffic).
      * ``hbm_bytes_accessed*`` — from each COMPILED executable's
        ``memory_analysis()``: the program's argument-buffer bytes, summed
        over the AOT serve programs plus a directly-lowered quantized
        forward. Every argument buffer is streamed from device memory exactly
        once per execution, so this is the per-step HBM read traffic the
        weights actually cost — and it is provably int8-sized for the
        quantized programs. This is the measure the 0.55x gate uses."""
    import jax
    import jax.numpy as jnp
    from flax import nnx

    import timm_tpu
    from ..parallel import build_quant_shardings, create_mesh, quant_path_specs
    from ..parallel.sharding import _kp_str
    from ..quantize import dequantize_tree, quantize_tree
    from ..serve import InferenceEngine

    metrics: Dict = {}

    # A/B engines on the default single-device serving mesh
    eng_fp = InferenceEngine(buckets=cfg.buckets)
    eng_fp.add_model(cfg.model, **cfg.kwargs())
    eng_q = InferenceEngine(buckets=cfg.buckets)
    eng_q.add_model(cfg.model, quantize='int8', **cfg.kwargs())

    fp_bytes = eng_fp.pool.acquire(cfg.model).param_bytes
    q_bytes = eng_q.pool.acquire(cfg.model).param_bytes
    metrics['param_bytes_fp32'] = int(fp_bytes)
    metrics['param_bytes_int8'] = int(q_bytes)
    metrics['quant_param_bytes_ratio'] = round(q_bytes / max(fp_bytes, 1), 4)

    def _exe_stats(exe):
        """(cost-model bytes-accessed | None, flops, compiled argument bytes)."""
        ca = _cost_analysis(exe, cfg.name)
        accessed = float(ca['bytes accessed']) if 'bytes accessed' in ca else None
        flops = float(ca.get('flops', 0.0))
        try:
            arg_bytes = int(exe.memory_analysis().argument_size_in_bytes)
        except Exception:
            arg_bytes = 0
        return accessed, flops, arg_bytes

    def _engine_stats(eng):
        total, have, flops, args = 0.0, False, 0.0, 0
        for bucket, exe in sorted(eng.aot_executables(cfg.model).items()):
            accessed, f, a = _exe_stats(exe)
            if accessed is not None:
                total, have = total + accessed, True
            flops += f
            args += a
        return (total if have else None), flops, args

    fp_accessed, _fp_flops, fp_args = _engine_stats(eng_fp)
    q_accessed, q_flops, q_args = _engine_stats(eng_q)
    if q_flops:
        metrics['flops'] = q_flops
    if fp_accessed is not None and q_accessed is not None:
        metrics['bytes_accessed_fp32'] = fp_accessed
        metrics['bytes_accessed'] = q_accessed
    metrics['serve_programs'] = (
        set(eng_fp.aot_executables(cfg.model)) == set(cfg.buckets)
        and set(eng_q.aot_executables(cfg.model)) == set(cfg.buckets))
    report = eng_q.donation_report(cfg.model)
    metrics['serve_donation_declared'] = bool(report) and all(
        r['declared'] for r in report.values())

    # the "quantized forward" twin pair: the same model lowered directly
    # (no engine plumbing) at the smallest bucket's batch shape
    model = timm_tpu.create_model(cfg.model, **cfg.kwargs())
    model.eval()
    graphdef, state = nnx.split(model)
    qstate = quantize_tree(state)
    img = cfg.img(224)
    x = jnp.zeros((min(cfg.buckets), img, img, 3), jnp.float32)

    def fwd_fp(s, xx):
        return nnx.merge(graphdef, s)(xx)

    def fwd_q(qs, xx):
        return nnx.merge(graphdef, dequantize_tree(qs))(xx)

    for bucket, exe in sorted(eng_q.aot_executables(cfg.model).items()):
        _capture(cfg.name, f'{cfg.name}/bucket{bucket}', 'serve_bucket',
                 compiled=exe, donation='declared',
                 declared=bool(report.get(bucket, {}).get('declared')))

    fp_fwd_compiled = jax.jit(fwd_fp).lower(state, x).compile()
    q_fwd_compiled = jax.jit(fwd_q).lower(qstate, x).compile()
    _capture(cfg.name, f'{cfg.name}/fwd_int8', 'fwd', compiled=q_fwd_compiled)
    _, _, fp_fwd_args = _exe_stats(fp_fwd_compiled)
    _, _, q_fwd_args = _exe_stats(q_fwd_compiled)

    hbm_fp = fp_args + fp_fwd_args
    hbm_q = q_args + q_fwd_args
    metrics['hbm_bytes_accessed_fp32'] = int(hbm_fp)
    metrics['hbm_bytes_accessed_int8'] = int(hbm_q)
    metrics['quant_bytes_accessed_ratio'] = round(hbm_q / max(hbm_fp, 1), 4)
    metrics['quant_halves_hbm'] = bool(
        metrics['quant_param_bytes_ratio'] <= 0.55
        and metrics['quant_bytes_accessed_ratio'] <= 0.55)

    # sharding legality on a real 3-axis mesh: place the int8 pytree under
    # build_quant_shardings and verify, from the PLACED arrays, that every
    # leaf landed on its resolved spec (qvalues through the unchanged rule
    # table, scales inheriting their kernel's last axis)
    mesh = create_mesh(fsdp=2, tp=2)
    specs = quant_path_specs(qstate, mesh)
    placed = jax.device_put(qstate, build_quant_shardings(qstate, mesh))
    flat, _ = jax.tree_util.tree_flatten_with_path(placed)
    placement_ok = len(qstate['scales']) > 0
    scales_sharded = 0
    for kp, leaf in flat:
        path = _kp_str(kp)
        spec = getattr(leaf.sharding, 'spec', None)
        placement_ok = placement_ok and tuple(spec or ()) == tuple(specs[path])
        if path.startswith('scales.') and tuple(spec or ()):
            scales_sharded += 1
    metrics['quant_sharding_ok'] = bool(placement_ok)
    # at least the tp column-parallel kernels' scales must actually shard —
    # inheritance degenerating to replicate-everything would silently pass
    # a pure equality check
    metrics['quant_scales_sharded'] = int(scales_sharded)
    return metrics


def _probe_elastic(cfg: ProbeConfig) -> Dict:
    """Elastic-resize legality (resilience/elastic.py): checkpoint state
    captured under the pre-resize mesh (all devices, fsdp=cfg.fsdp) re-places
    under the post-resize half-pod mesh with the fsdp axis clamped the way
    ``plan_elastic_resume`` would clamp it, and the re-placed task's train
    step still lowers with its state donation aliased.

      * ``elastic_resharding_ok``   — every re-placed param landed on the NEW
        mesh with at least one leaf actually sharded over 'fsdp', and the
        values round-tripped bit-exactly through the host snapshot;
      * ``elastic_global_batch_ok`` — the rescale solver returns a
        (batch, accum) pair that preserves the global batch and shards evenly
        on the post-resize mesh;
      * ``donation_aliases`` / ``donation_ok`` — the usual HLO alias-table
        evidence, for the step compiled AFTER the resize re-placement.

    No trace_ms: this probe pins legality, not trace cost."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax import nnx

    import timm_tpu
    from ..loss import LabelSmoothingCrossEntropy
    from ..optim import create_optimizer_v2
    from ..parallel import create_mesh, resolve_elastic_axes, set_global_mesh, shard_batch
    from ..resilience import rescale_for_devices, snapshot_to_host
    from ..task import ClassificationTask

    def build(mesh):
        model = timm_tpu.create_model(cfg.model, **cfg.kwargs())
        return ClassificationTask(
            model, optimizer=create_optimizer_v2(model, opt=cfg.opt, lr=0.1),
            mesh=mesh, train_loss_fn=LabelSmoothingCrossEntropy(0.1))

    # pre-resize: the dead run's full-pod mesh
    mesh_from = create_mesh(fsdp=cfg.fsdp)
    set_global_mesh(mesh_from)
    state = snapshot_to_host(build(mesh_from).get_checkpoint_state())

    # post-resize: half the devices survive; clamp the axes as the planner does
    devices = jax.devices()
    n_to = max(1, len(devices) // 2)
    fsdp_to, tp_to = resolve_elastic_axes(n_to, fsdp=cfg.fsdp, tp=cfg.tp)
    mesh_to = create_mesh(devices=devices[:n_to], fsdp=fsdp_to, tp=tp_to)
    set_global_mesh(mesh_to)
    task_to = build(mesh_to)
    task_to.load_checkpoint_state(state)

    metrics: Dict = {'elastic_devices_from': len(devices), 'elastic_devices_to': n_to}
    params = nnx.state(task_to.model, nnx.Param)
    on_new_mesh, fsdp_sharded = True, False
    for leaf in jax.tree.leaves(params):
        sharding = getattr(getattr(leaf, 'value', leaf), 'sharding', None)
        on_new_mesh = on_new_mesh and getattr(sharding, 'mesh', None) == mesh_to
        fsdp_sharded = fsdp_sharded or 'fsdp' in tuple(getattr(sharding, 'spec', ()) or ())
    # bit-exact round trip through the host snapshot for one witness leaf
    key = next(k for k in state if k.startswith('state_dict.'))
    reloaded = snapshot_to_host(task_to.get_checkpoint_state())
    values_ok = np.array_equal(state[key], reloaded[key])
    metrics['elastic_resharding_ok'] = bool(on_new_mesh and fsdp_sharded and values_ok)

    global_batch = cfg.batch_size * cfg.grad_accum
    bs, accum = rescale_for_devices(global_batch, mesh_to.size,
                                    prefer_batch_size=cfg.batch_size)
    metrics['elastic_global_batch_ok'] = bool(
        bs * accum == global_batch and bs % mesh_to.size == 0)

    rng = np.random.RandomState(0)
    s = cfg.img(224)
    num_classes = int(cfg.kwargs().get('num_classes', 1000))
    batch = shard_batch({'input': jnp.asarray(rng.rand(bs, s, s, 3), jnp.float32),
                         'target': jnp.asarray(rng.randint(0, num_classes, bs))},
                        mesh_to)
    compiled = task_to.lower_train_step(batch, lr=0.1)
    _capture(cfg.name, f'{cfg.name}/train_step_postresize', 'train_step',
             compiled=compiled, donation='alias')
    ev = donation_evidence(compiled)
    metrics['donation_aliases'] = ev['aliases']
    metrics['donation_ok'] = ev['aliases'] > 0
    return metrics


def probe_config(cfg: ProbeConfig) -> Dict:
    """Probe one config; global mesh is saved/restored so probes compose with
    whatever mesh the calling process (tests, a CLI) had active."""
    from ..parallel import mesh as mesh_mod

    saved = mesh_mod.peek_global_mesh()
    try:
        if cfg.collect == 'serve':
            return _probe_serve(cfg)
        if cfg.collect == 'quant':
            return _probe_quant(cfg)
        if cfg.collect == 'augment':
            return _probe_augment(cfg)
        if cfg.collect == 'naflex':
            return _probe_naflex(cfg)
        if cfg.collect == 'elastic':
            return _probe_elastic(cfg)
        return _probe_train(cfg)
    finally:
        mesh_mod._GLOBAL_MESH = saved


def run_matrix(configs: Optional[Sequence[ProbeConfig]] = None,
               names: Optional[Sequence[str]] = None,
               log=None) -> Dict[str, Dict]:
    """Probe the matrix (default: DEFAULT_MATRIX, optionally filtered by
    `names`) -> {config_name: metrics}."""
    configs = list(configs) if configs is not None else list(DEFAULT_MATRIX)
    if names is not None:
        wanted = set(names)
        unknown = wanted - {c.name for c in configs}
        if unknown:
            raise ValueError(f'unknown probe config(s): {sorted(unknown)}')
        configs = [c for c in configs if c.name in wanted]
    out: Dict[str, Dict] = {}
    for cfg in configs:
        t0 = time.perf_counter()
        out[cfg.name] = probe_config(cfg)
        if log is not None:
            log(f'perfbudget probe {cfg.name} [{cfg.collect}] '
                f'({time.perf_counter() - t0:.1f}s): '
                f'{len(out[cfg.name])} metrics')
    return out
