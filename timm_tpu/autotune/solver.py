"""The autotuner: enumerate -> rank -> (optionally) probe -> apply.

`autotune()` is the one entry point behind every surface: `train.py
--autotune`, `python -m timm_tpu.autotune`, and the elastic re-solve
(:func:`resolve_config_for_topology`). It holds the global batch exactly
constant — the same invariant elastic resume enforces — and only searches
placement/decomposition.

Elastic policy ("first, do no harm"): the re-solver returns the REQUESTED
config unchanged whenever it is legal on the live topology, so a working
run never churns its mesh (and the 8<->4 drill parity bound is untouched).
Only when the requested point is illegal — exactly when the old
largest-divisor clamp would have kicked in — does the cost model pick the
replacement, and the clamp remains the documented fallback when the solver
itself refuses (no model dims, no legal point, any internal error).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from .cost import (
    CostEstimate, DeviceClass, analytic_cost, default_hbm_budget,
    detect_device_class, fit_scales, probed_cost,
)
from .space import CandidateConfig, LegalPoint, Rejection, enumerate_configs

__all__ = ['AutotuneError', 'AutotuneResult', 'RankedPoint', 'autotune',
           'abstract_model_info', 'format_table', 'to_json', 'apply_to_args',
           'resolve_config_for_topology']


class AutotuneError(RuntimeError):
    """The solver cannot rank this request (no legal points, no model dims,
    ...). Carries the rejections so callers can print WHY."""

    def __init__(self, msg: str, rejections: Sequence[Rejection] = ()):
        super().__init__(msg)
        self.rejections = list(rejections)


@dataclasses.dataclass(frozen=True)
class RankedPoint:
    rank: int
    point: LegalPoint
    cost: CostEstimate
    probed: Optional[CostEstimate] = None   # set for the --probe-top-k shortlist

    @property
    def best(self) -> CostEstimate:
        return self.probed if self.probed is not None else self.cost

    @property
    def agreement(self) -> Optional[float]:
        """estimator/probed step-time ratio for shortlist points (the
        correction-factor protocol watches this band)."""
        if self.probed is None or self.probed.step_ms <= 0:
            return None
        return self.cost.step_ms / self.probed.step_ms


@dataclasses.dataclass
class AutotuneResult:
    model: str
    n_devices: int
    global_batch: int
    device_class: DeviceClass
    hbm_budget_bytes: int
    tier: str                       # best tier that ran: analytic|estimator|probed
    ranked: List[RankedPoint]
    rejections: List[Rejection]
    correction: float
    anchor: Dict                    # {'config': label, 'flops': ..., ...} or {}

    @property
    def winner(self) -> CandidateConfig:
        return self.ranked[0].point.config


def abstract_model_info(model: str, model_kwargs: Optional[Dict] = None):
    """(abstract param pytree, (seq_len, width, depth) or None, mlp_ratio)
    without materializing a single array: `nnx.eval_shape` runs the model
    constructor abstractly, and the probe helper reads the ViT dims off it."""
    from flax import nnx

    import timm_tpu
    from ..perfbudget.probe import _model_dims

    kwargs = dict(model_kwargs or {})
    try:
        abs_model = nnx.eval_shape(lambda: timm_tpu.create_model(model, **kwargs))
    except TypeError as e:
        # mirror train.py's _build_model: fixed-field models take no img_size
        if 'img_size' not in str(e) or 'img_size' not in kwargs:
            raise
        kwargs.pop('img_size')
        abs_model = nnx.eval_shape(lambda: timm_tpu.create_model(model, **kwargs))
    params = nnx.state(abs_model, nnx.Param)
    dims = _model_dims(abs_model)
    mlp_ratio = 4.0
    blocks = getattr(abs_model, 'blocks', None)
    try:
        fc1 = blocks[0].mlp.fc1.kernel.shape  # type: ignore[index]
        mlp_ratio = float(fc1[1]) / float(fc1[0])
    except (TypeError, AttributeError, IndexError, KeyError):
        pass
    return params, dims, mlp_ratio


def _probe_point(model: str, model_kwargs: Optional[Dict],
                 cfg: CandidateConfig, name: str) -> Dict:
    """Lower the REAL TrainingTask step for one candidate via the perfbudget
    probe (collect='full': compiled flops/bytes/donation + trace time)."""
    from ..perfbudget.probe import ProbeConfig, probe_config

    return probe_config(ProbeConfig(
        name=name, model=model,
        model_kwargs=tuple(sorted((model_kwargs or {}).items())),
        batch_size=cfg.batch_size, fsdp=cfg.fsdp, tp=cfg.tp,
        block_scan=cfg.block_scan if cfg.block_scan is not None else None,
        grad_accum=cfg.grad_accum, collect='full'))


def autotune(
        model: str,
        model_kwargs: Optional[Dict] = None,
        *,
        global_batch: int,
        n_devices: Optional[int] = None,
        num_slices: int = 1,
        hbm_budget_bytes: Optional[int] = None,
        probe_top_k: int = 0,
        probe_anchor: bool = True,
        anchor_metrics: Optional[Dict] = None,
        anchor_config: Optional[CandidateConfig] = None,
        max_accum: int = 64,
        allow_tp: bool = True,
        allow_remat: bool = True,
        include_block_scan: bool = True,
        fsdp_candidates: Optional[Sequence[int]] = None,
        tp_candidates: Optional[Sequence[int]] = None,
        device_class: Optional[DeviceClass] = None,
        correction: float = 1.0,
        log=None,
) -> AutotuneResult:
    """Rank every legal config for `model` at a fixed global batch.

    Tier selection: with ``anchor_metrics`` (or ``probe_anchor=True``) the
    estimator tier calibrates the analytic model against one probed anchor;
    ``probe_top_k > 0`` additionally lowers the shortlist's real programs
    and re-ranks it on their compiled costs. ``probe_anchor=False`` with no
    metrics runs the pure-analytic tier (the elastic re-solve path — zero
    lowering in the restart pre-pass)."""
    import jax

    n_devices = int(n_devices) if n_devices else jax.device_count()
    dc = device_class or detect_device_class()
    budget = hbm_budget_bytes if hbm_budget_bytes is not None else default_hbm_budget(dc)
    correction = float(correction)

    params, dims, mlp_ratio = abstract_model_info(model, model_kwargs)
    if dims is None:
        raise AutotuneError(
            f'autotune: model {model!r} exposes no (pos_embed, blocks) ViT '
            f'dims — the analytic cost model cannot rank it (fallback: run '
            f'the probed tier per config by hand via perfbudget)')

    legal, rejections = enumerate_configs(
        n_devices=n_devices, global_batch=global_batch, params=params,
        model_dims=dims, hbm_budget_bytes=budget, num_slices=num_slices,
        max_accum=max_accum, allow_tp=allow_tp, allow_remat=allow_remat,
        include_block_scan=include_block_scan,
        fsdp_candidates=fsdp_candidates, tp_candidates=tp_candidates,
        mlp_ratio=mlp_ratio)
    if not legal:
        raise AutotuneError(
            f'autotune: no legal config for {model!r} at global batch '
            f'{global_batch} on {n_devices} devices — '
            + '; '.join(str(r) for r in rejections[:4]), rejections)
    if log:
        log(f'autotune: {len(legal)} legal points, {len(rejections)} rejected '
            f'({dc.name}, budget {budget / 2**30:.1f} GiB/device)')

    # ---- anchor (estimator tier) -------------------------------------------
    tier = 'analytic'
    anchor_info: Dict = {}
    flops_scale = bytes_scale = 1.0
    by_cfg = {p.config: p for p in legal}
    if anchor_metrics is None and probe_anchor:
        a_cfg = anchor_config or _default_anchor(legal)
        anchor_metrics = _probe_point(model, model_kwargs, a_cfg, 'autotune_anchor')
        anchor_config = a_cfg
    if anchor_metrics is not None:
        a_cfg = anchor_config or _default_anchor(legal)
        a_point = by_cfg.get(a_cfg) or _anchor_point(
            a_cfg, params, dims, n_devices, num_slices, mlp_ratio)
        flops_scale, bytes_scale = fit_scales(
            anchor_metrics, a_point, dims, dc, n_devices, mlp_ratio)
        tier = 'estimator'
        anchor_info = {'config': a_cfg.label(),
                       'flops': anchor_metrics.get('flops'),
                       'bytes_accessed': anchor_metrics.get('bytes_accessed'),
                       'flops_scale': round(flops_scale, 4),
                       'bytes_scale': round(bytes_scale, 4)}
        if log:
            log(f'autotune: anchor {a_cfg.label()} -> scales '
                f'flops x{flops_scale:.3g}, bytes x{bytes_scale:.3g}')

    # ---- rank ---------------------------------------------------------------
    scored = [(p, analytic_cost(p, dims, dc, n_devices, mlp_ratio=mlp_ratio,
                                flops_scale=flops_scale, bytes_scale=bytes_scale,
                                correction=correction, tier=tier))
              for p in legal]
    scored.sort(key=lambda pc: pc[1].sort_key() + _stable_key(pc[0].config))

    # ---- probe the shortlist (--probe-top-k) --------------------------------
    probed: Dict[CandidateConfig, CostEstimate] = {}
    if probe_top_k > 0:
        for i, (p, _c) in enumerate(scored[:probe_top_k]):
            metrics = _probe_point(model, model_kwargs, p.config,
                                   f'autotune_probe{i}')
            est = probed_cost(metrics, p, dc, correction=correction)
            if est is not None:
                probed[p.config] = est
            if log:
                log(f'autotune: probed #{i + 1} {p.config.label()} -> '
                    + (f'{est.step_ms:.3f} ms ({est.bound}-bound)' if est
                       else 'no cost analysis (ranked by estimator)'))
        if probed:
            tier = 'probed'
            # re-rank the shortlist on real compiled costs; the tail keeps
            # its estimator order below every probed point's re-ranked slot
            head = sorted(scored[:probe_top_k],
                          key=lambda pc: (probed.get(pc[0].config, pc[1]).sort_key()
                                          + _stable_key(pc[0].config)))
            scored = head + scored[probe_top_k:]

    ranked = [RankedPoint(rank=i + 1, point=p, cost=c,
                          probed=probed.get(p.config))
              for i, (p, c) in enumerate(scored)]
    return AutotuneResult(model=model, n_devices=n_devices,
                          global_batch=int(global_batch), device_class=dc,
                          hbm_budget_bytes=int(budget), tier=tier,
                          ranked=ranked, rejections=rejections,
                          correction=correction, anchor=anchor_info)


def _stable_key(cfg: CandidateConfig) -> Tuple:
    """Total-order tail so equal-cost points rank deterministically:
    prefer larger batch (fewer sequential micro-steps), then smaller axes,
    scan on, remat off."""
    return (cfg.grad_accum, cfg.fsdp, cfg.tp, not cfg.block_scan, cfg.remat)


def _default_anchor(legal: Sequence[LegalPoint]) -> CandidateConfig:
    """Deterministic anchor: the cheapest-to-lower legal point — smallest
    batch, no tp, smallest fsdp, scanned, no remat, accum=1."""
    def key(p: LegalPoint):
        c = p.config
        return (c.tp != 1, c.fsdp != 1, c.batch_size, c.grad_accum,
                not c.block_scan, c.remat)
    base = min(legal, key=key).config
    return dataclasses.replace(base, grad_accum=1, remat=False,
                               block_scan=True,
                               batch_size=min(p.config.batch_size for p in legal))


def _anchor_point(cfg: CandidateConfig, params, dims, n_devices: int,
                  num_slices: int, mlp_ratio: float) -> LegalPoint:
    """LegalPoint byte estimates for an anchor that is not in the enumerated
    set (e.g. its batch does not divide the requested global batch)."""
    pts, _rej = enumerate_configs(
        n_devices=n_devices, global_batch=cfg.global_batch, params=params,
        model_dims=dims, hbm_budget_bytes=None, num_slices=num_slices,
        allow_tp=cfg.tp > 1, allow_remat=cfg.remat,
        include_block_scan=not cfg.block_scan,
        fsdp_candidates=(cfg.fsdp,), tp_candidates=(cfg.tp,),
        mlp_ratio=mlp_ratio)
    for p in pts:
        if p.config == cfg:
            return p
    raise AutotuneError(f'anchor config {cfg.label()} is not legal on this topology')


# ---- output surfaces --------------------------------------------------------

def format_table(result: AutotuneResult, top: int = 10) -> str:
    """The ranked table `train.py --autotune` prints."""
    dc = result.device_class
    lines = [
        f'autotune: {result.model} | global batch {result.global_batch} | '
        f'{result.n_devices}x {dc.name} ({dc.peak_flops / 1e12:.0f} TF/s, '
        f'{dc.hbm_bw / 1e9:.0f} GB/s, budget '
        f'{result.hbm_budget_bytes / 2**30:.1f} GiB) | tier: {result.tier}'
        + (f' | correction x{result.correction:.3f}'
           if result.correction != 1.0 else ''),
        f'{"#":>3} {"config":<38} {"ms/step":>9} {"bound":>7} '
        f'{"GiB/dev":>8} {"tier":>9} {"est/probe":>9}',
    ]
    for rp in result.ranked[:top]:
        est = rp.best
        agree = f'{rp.agreement:.2f}' if rp.agreement is not None else '-'
        lines.append(
            f'{rp.rank:>3} {rp.point.config.label():<38} {est.step_ms:>9.3f} '
            f'{est.bound:>7} {rp.point.hbm_bytes / 2**30:>8.2f} '
            f'{est.tier:>9} {agree:>9}')
    if result.rejections:
        lines.append(f'pruned {len(result.rejections)} illegal point(s); first:')
        for r in result.rejections[:3]:
            lines.append(f'  - {r}')
    lines.append(f'winner: {result.winner.label()}  ->  {result.winner.flags()}')
    return '\n'.join(lines)


def to_json(result: AutotuneResult, top: Optional[int] = None) -> Dict:
    """The machine surface (`python -m timm_tpu.autotune`)."""
    def cost_dict(c: Optional[CostEstimate]):
        if c is None:
            return None
        return {'step_ms': round(c.step_ms, 6), 'bound': c.bound,
                'tier': c.tier, 'flops': c.flops, 'bytes': c.bytes,
                'compute_ms': round(c.compute_ms, 6),
                'memory_ms': round(c.memory_ms, 6)}

    return {
        'schema': 'autotune/v1',
        'model': result.model,
        'n_devices': result.n_devices,
        'global_batch': result.global_batch,
        'device_class': result.device_class.name,
        'hbm_budget_bytes': result.hbm_budget_bytes,
        'tier': result.tier,
        'correction': result.correction,
        'anchor': result.anchor,
        'winner': dataclasses.asdict(result.winner),
        'winner_flags': result.winner.flags(),
        'ranked': [{
            'rank': rp.rank,
            'config': dataclasses.asdict(rp.point.config),
            'hbm_bytes': rp.point.hbm_bytes,
            'cost': cost_dict(rp.cost),
            'probed': cost_dict(rp.probed),
            'agreement': rp.agreement,
        } for rp in (result.ranked[:top] if top else result.ranked)],
        'rejections': [{'point': r.point, 'reason': r.reason,
                        'suggestion': r.suggestion} for r in result.rejections],
    }


def apply_to_args(args, result: AutotuneResult) -> List[str]:
    """Write the winner's flags onto a train.py argparse namespace; returns
    human-readable change notes for the resume log."""
    w = result.winner
    notes = []

    def set_attr(name, new, old):
        if new != old:
            notes.append(f'{name}: {old} -> {new}')
        setattr(args, name, new)

    set_attr('fsdp', w.fsdp if w.fsdp > 1 else 0, getattr(args, 'fsdp', 0))
    set_attr('tp', w.tp if w.tp > 1 else 0, getattr(args, 'tp', 0))
    set_attr('batch_size', w.batch_size, getattr(args, 'batch_size', None))
    set_attr('grad_accum_steps', w.grad_accum,
             getattr(args, 'grad_accum_steps', 1))
    set_attr('block_scan', bool(w.block_scan), getattr(args, 'block_scan', False))
    set_attr('grad_checkpointing', bool(w.remat),
             getattr(args, 'grad_checkpointing', False))
    return notes


# ---- elastic re-solve -------------------------------------------------------

def resolve_config_for_topology(
        n_devices: int,
        global_batch: int,
        *,
        model: str,
        model_kwargs: Optional[Dict] = None,
        fsdp: Optional[int] = None,
        tp: Optional[int] = None,
        prefer_batch_size: Optional[int] = None,
        num_slices: int = 1,
        max_accum: int = 64,
) -> Optional[CandidateConfig]:
    """Re-solve (fsdp, tp, batch_size, accum) for a changed topology,
    holding the global batch exactly constant. Returns None when the solver
    refuses (caller falls back to the largest-divisor clamp + rescale).

    Policy (see module docstring): if the REQUESTED config is legal on the
    live topology it is returned unchanged — a working run never churns its
    mesh, and at an unchanged topology the re-solve is the identity. Only
    an illegal request is re-solved, by analytic-roofline rank (no lowering
    happens in the restart pre-pass), with the batch-size preference as the
    final tie-break."""
    fsdp_req = int(fsdp) if fsdp and int(fsdp) > 1 else 1
    tp_req = int(tp) if tp and int(tp) > 1 else 1
    result = autotune(
        model, model_kwargs, global_batch=int(global_batch),
        n_devices=int(n_devices), num_slices=num_slices, max_accum=max_accum,
        allow_tp=tp_req > 1, allow_remat=False, include_block_scan=False,
        probe_anchor=False, correction=1.0)

    prefer = int(prefer_batch_size) if prefer_batch_size else int(global_batch)
    legal = {rp.point.config: rp for rp in result.ranked}

    # identity fast-path: the requested point, if legal, wins outright
    if prefer_batch_size:
        requested = CandidateConfig(
            fsdp=fsdp_req, tp=tp_req, batch_size=prefer,
            grad_accum=int(global_batch) // max(prefer, 1),
            block_scan=True, remat=False)
        if requested.global_batch == int(global_batch) and requested in legal:
            return requested

    # otherwise: best cost, preferring the requested axes and batch among
    # near-ties (same step_ms after rounding)
    best = min(legal.values(), key=lambda rp: rp.cost.sort_key() + (
        abs(rp.point.config.fsdp - fsdp_req),
        abs(rp.point.config.tp - tp_req),
        abs(rp.point.config.batch_size - prefer),
        _stable_key(rp.point.config)))
    return best.point.config
