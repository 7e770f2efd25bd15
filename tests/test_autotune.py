"""Autotuner tests: legal-space enumeration, roofline ranking, estimator vs
probed agreement, elastic re-solve, bucket-ladder DP.

Everything here runs on the forced 8-virtual-CPU-device topology
(conftest.py). The one real lowering (the estimator/probed agreement band)
reuses the session-scoped `analysis_programs` probe run as its anchor plus a
single extra compile that rides the persistent compile cache; the
`train.py --autotune` subprocess smoke is `-m slow` with the in-process CLI
twin kept in tier-1.
"""
import argparse
import itertools
import json
import logging
import os
import subprocess
import sys

import pytest

import timm_tpu  # noqa: F401  — device topology + registry side effects

pytestmark = pytest.mark.autotune

MODEL_KW = {'num_classes': 10, 'img_size': 32}
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _abstract_info():
    from timm_tpu.autotune.solver import abstract_model_info
    return abstract_model_info('test_vit', MODEL_KW)


# ---- enumerator legality ----------------------------------------------------

def test_enumerator_points_build_real_meshes_and_pass_partition_lint():
    import jax

    from timm_tpu.autotune import enumerate_configs
    from timm_tpu.parallel.mesh import create_mesh
    from timm_tpu.parallel.sharding import _kp_str, path_specs

    params, dims, _ = _abstract_info()
    legal, _rej = enumerate_configs(n_devices=8, global_batch=64,
                                    params=params, model_dims=dims)
    assert legal, 'no legal configs for the canonical tiny space'

    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    seen_pairs = set()
    for p in legal:
        cfg = p.config
        # batch decomposition holds the global batch and the shard rule
        assert cfg.batch_size * cfg.grad_accum == 64
        assert cfg.batch_size % 8 == 0
        assert p.hbm_bytes == p.param_bytes * 2 + p.opt_bytes + p.act_bytes
        if (cfg.fsdp, cfg.tp) in seen_pairs:
            continue
        seen_pairs.add((cfg.fsdp, cfg.tp))
        # the emitted axes build a REAL mesh...
        mesh = create_mesh(fsdp=cfg.fsdp if cfg.fsdp > 1 else None,
                           tp=cfg.tp if cfg.tp > 1 else None)
        assert mesh.size == 8
        # ...and every param's resolved spec divides its dims evenly
        specs = path_specs(params, mesh)
        for kp, leaf in flat:
            spec = specs[_kp_str(kp)]
            for dim, ax in zip(leaf.shape, tuple(spec)):
                if ax is None:
                    continue
                shards = 1
                for a in (ax if isinstance(ax, tuple) else (ax,)):
                    shards *= int(mesh.shape[a])
                assert dim % shards == 0, (
                    f'{_kp_str(kp)}: dim {dim} not divisible by {shards} '
                    f'(fsdp={cfg.fsdp}, tp={cfg.tp})')
    assert (1, 1) in seen_pairs and (8, 1) in seen_pairs


def test_illegal_global_batch_refused_with_nearest_legal_text():
    from timm_tpu.autotune import AutotuneError, autotune, enumerate_configs

    legal, rej = enumerate_configs(n_devices=8, global_batch=30)
    assert not legal
    msg = ' '.join(str(r) for r in rej)
    assert 'nearest legal global batch' in msg
    assert '24 or 32' in msg

    with pytest.raises(AutotuneError) as ei:
        autotune('test_vit', MODEL_KW, global_batch=30, probe_anchor=False)
    assert 'no legal config' in str(ei.value)
    assert ei.value.rejections


def test_illegal_mesh_axes_rejected_with_clamp_suggestion():
    from timm_tpu.autotune import mesh_axis_points

    pairs, rej = mesh_axis_points(8, fsdp_candidates=(3,), tp_candidates=(1,))
    assert pairs == []
    assert len(rej) == 1
    assert 'does not divide' in rej[0].reason
    assert 'fsdp=2 tp=1' in rej[0].suggestion


def test_hbm_budget_rejections_are_loud():
    from timm_tpu.autotune import enumerate_configs

    params, dims, _ = _abstract_info()
    legal, rej = enumerate_configs(n_devices=8, global_batch=64,
                                   params=params, model_dims=dims,
                                   hbm_budget_bytes=10 * 1024)
    assert not legal
    hbm_rej = [r for r in rej if 'HBM budget' in r.reason]
    assert hbm_rej
    assert any('remat' in r.suggestion or 'fsdp' in r.suggestion
               for r in hbm_rej)


# ---- roofline ranking -------------------------------------------------------

def test_roofline_monotone_in_flops_and_bytes():
    from timm_tpu.autotune import DEVICE_CLASSES, roofline_ms

    dc = DEVICE_CLASSES['v5e']
    base = roofline_ms(1e12, 1e9, dc)[0]
    assert roofline_ms(2e12, 1e9, dc)[0] >= base
    assert roofline_ms(1e12, 2e9, dc)[0] >= base
    # the bound label flips where the two service times cross
    assert roofline_ms(1e15, 1, dc)[3] == 'compute'
    assert roofline_ms(1, 1e12, dc)[3] == 'memory'


def test_analytic_ranking_is_deterministic_and_scan_wins_ties():
    from timm_tpu.autotune import autotune

    kw = dict(global_batch=64, probe_anchor=False, correction=1.0)
    r1 = autotune('test_vit', MODEL_KW, **kw)
    r2 = autotune('test_vit', MODEL_KW, **kw)
    assert [rp.point.config for rp in r1.ranked] == \
        [rp.point.config for rp in r2.ranked]
    assert r1.tier == 'analytic'
    assert r1.winner.block_scan, \
        'trace-penalty tiebreak must prefer the scanned program'
    # a no-scan twin of the winner exists and ranks strictly below it
    import dataclasses
    twin = dataclasses.replace(r1.winner, block_scan=False)
    ranks = {rp.point.config: rp.rank for rp in r1.ranked}
    assert ranks[twin] > ranks[r1.winner]


def test_correction_factor_scales_time_but_not_order():
    from timm_tpu.autotune import autotune

    r1 = autotune('test_vit', MODEL_KW, global_batch=64, probe_anchor=False,
                  correction=1.0)
    r2 = autotune('test_vit', MODEL_KW, global_batch=64, probe_anchor=False,
                  correction=2.0)
    assert [rp.point.config for rp in r2.ranked] == \
        [rp.point.config for rp in r1.ranked]
    assert r2.ranked[0].cost.step_ms == pytest.approx(
        2.0 * r1.ranked[0].cost.step_ms, rel=1e-6)


def test_v5e_peaks_agree_with_the_benchmarks_table():
    """Two peak tables remain until ROADMAP D6 falls: the cost model's and the
    one the ledger's `step_mfu.train` divides by. They may not drift."""
    from benchmarks.harness.peaks import peak
    from timm_tpu.autotune.cost import DEVICE_CLASSES

    v5e, ledger = DEVICE_CLASSES['v5e'], peak('TPU v5 lite')
    assert v5e.peak_flops == ledger['bf16_flops']
    assert v5e.hbm_bw == ledger['hbm_bytes_per_s']


# ---- estimator vs probed ----------------------------------------------------

def test_estimator_passes_exactly_through_probed_anchor(analysis_programs):
    from timm_tpu.autotune import CandidateConfig
    from timm_tpu.autotune.cost import (analytic_cost, detect_device_class,
                                        fit_scales, probed_cost)
    from timm_tpu.autotune.solver import _anchor_point

    anchor = analysis_programs['measured']['base']   # test_vit b=8 fsdp=1 tp=1
    assert 'flops' in anchor and 'bytes_accessed' in anchor
    params, dims, mlp = _abstract_info()
    dc = detect_device_class()
    a_cfg = CandidateConfig(batch_size=8)
    ap = _anchor_point(a_cfg, params, dims, 8, 1, mlp)

    fs, bs = fit_scales(anchor, ap, dims, dc, 8, mlp)
    est = analytic_cost(ap, dims, dc, 8, mlp_ratio=mlp,
                        flops_scale=fs, bytes_scale=bs, tier='estimator')
    pr = probed_cost(anchor, ap, dc)
    # calibration guarantee: at the anchor the estimator IS the probed cost
    assert est.flops == pytest.approx(pr.flops, rel=1e-9)
    assert est.bytes == pytest.approx(pr.bytes, rel=1e-9)
    assert est.step_ms == pytest.approx(pr.step_ms, rel=1e-9)


def test_estimator_vs_probed_agreement_band(analysis_programs):
    """Off-anchor, the estimator must stay within a (loose) multiplicative
    band of the probed roofline — the correction-factor protocol assumes the
    RANKING survives even though absolute CPU-class milliseconds are
    nominal. One extra compile (the fsdp4 matrix config's real train step),
    shared with the persistent compile cache."""
    from timm_tpu.autotune import CandidateConfig, enumerate_configs
    from timm_tpu.autotune.cost import (analytic_cost, detect_device_class,
                                        fit_scales, probed_cost)
    from timm_tpu.autotune.solver import _anchor_point
    from timm_tpu.perfbudget.probe import DEFAULT_MATRIX, probe_config

    anchor = analysis_programs['measured']['base']
    params, dims, mlp = _abstract_info()
    dc = detect_device_class()
    ap = _anchor_point(CandidateConfig(batch_size=8), params, dims, 8, 1, mlp)
    fs, bs = fit_scales(anchor, ap, dims, dc, 8, mlp)

    fsdp4 = next(c for c in DEFAULT_MATRIX if c.name == 'fsdp4')
    probed_metrics = probe_config(fsdp4)
    legal, _ = enumerate_configs(n_devices=8, global_batch=8, params=params,
                                 model_dims=dims, fsdp_candidates=(4,),
                                 tp_candidates=(1,), allow_remat=False,
                                 include_block_scan=False)
    point = next(p for p in legal
                 if p.config == CandidateConfig(fsdp=4, batch_size=8))
    est = analytic_cost(point, dims, dc, 8, mlp_ratio=mlp,
                        flops_scale=fs, bytes_scale=bs, tier='estimator')
    pr = probed_cost(probed_metrics, point, dc)
    assert pr is not None
    ratio = est.step_ms / pr.step_ms
    assert 0.1 <= ratio <= 10.0, (
        f'estimator/probed = {ratio:.3f} outside the agreement band '
        f'(est {est.step_ms:.4f} ms vs probed {pr.step_ms:.4f} ms)')


# ---- elastic re-solve -------------------------------------------------------

def test_elastic_resolve_identity_at_unchanged_topology():
    from timm_tpu.autotune import CandidateConfig, resolve_config_for_topology

    cfg = resolve_config_for_topology(
        8, 8, model='test_vit', model_kwargs=MODEL_KW,
        fsdp=4, tp=None, prefer_batch_size=8)
    assert cfg == CandidateConfig(fsdp=4, tp=1, batch_size=8, grad_accum=1)


def test_plan_elastic_resume_solver_matches_clamp_when_request_legal():
    from timm_tpu.resilience.elastic import plan_elastic_resume

    with_solver = plan_elastic_resume(8, batch_size=8, grad_accum=1, fsdp=4,
                                      model='test_vit', model_kwargs=MODEL_KW)
    clamp_only = plan_elastic_resume(8, batch_size=8, grad_accum=1, fsdp=4)
    for field in ('devices', 'fsdp', 'tp', 'batch_size', 'grad_accum',
                  'global_batch'):
        assert getattr(with_solver, field) == getattr(clamp_only, field), field
    assert not any('re-solved' in n for n in with_solver.notes)


def test_elastic_resize_8_to_4_keeps_requested_legal_config():
    # the 8->4 drill geometry: fsdp=4, b=8 is STILL legal on 4 devices, so
    # the re-solve is the identity and the drill's 1e-6 parity bound holds
    from timm_tpu.autotune import CandidateConfig, resolve_config_for_topology

    cfg = resolve_config_for_topology(
        4, 8, model='test_vit', model_kwargs=MODEL_KW,
        fsdp=4, tp=None, prefer_batch_size=8)
    assert cfg == CandidateConfig(fsdp=4, tp=1, batch_size=8, grad_accum=1)


def test_elastic_resolve_replaces_illegal_request():
    from timm_tpu.autotune import resolve_config_for_topology

    # fsdp=8 cannot exist on 4 devices: the solver must re-solve, holding
    # the global batch, and prefer axes near the request
    cfg = resolve_config_for_topology(
        4, 8, model='test_vit', model_kwargs=MODEL_KW,
        fsdp=8, tp=None, prefer_batch_size=8)
    assert cfg is not None
    assert cfg.global_batch == 8
    assert 4 % (cfg.fsdp * cfg.tp) == 0
    assert cfg.fsdp == 4, 'nearest legal fsdp to the requested 8 on 4 devices'


def test_plan_elastic_resume_falls_back_when_solver_refuses():
    from timm_tpu.resilience.elastic import plan_elastic_resume

    plan = plan_elastic_resume(8, batch_size=8, grad_accum=1, fsdp=4,
                               model='not_a_registered_model')
    assert plan.fsdp == 4 and plan.batch_size == 8 and plan.grad_accum == 1
    assert any('falling back to the largest-divisor clamp' in n
               for n in plan.notes)


# ---- bucket-ladder DP -------------------------------------------------------

def test_bucket_dp_matches_brute_force():
    from timm_tpu.autotune import ladder_cost, propose_buckets

    hist = {1: 7, 3: 2, 4: 11, 6: 1, 9: 5, 16: 3}
    sizes = sorted(hist)
    for k in (1, 2, 3, 4):
        # brute force over ladders covering the largest observed size (the
        # DP's covering constraint — no request is ever chunked)
        best = min(ladder_cost(c, hist)
                   for r in range(1, k + 1)
                   for c in itertools.combinations(sizes, r)
                   if max(sizes) in c)
        got = propose_buckets(hist, max_buckets=k)
        assert len(got) <= k
        assert max(got) == max(sizes)
        assert ladder_cost(got, hist) == best, (k, got)


def test_propose_buckets_divisor_cap_determinism_and_empty():
    from timm_tpu.autotune import ladder_waste, propose_buckets

    hist = {3: 5, 7: 1}
    got = propose_buckets(hist, max_buckets=2, divisor=4)
    assert all(b % 4 == 0 for b in got)
    assert max(got) >= 7

    capped = propose_buckets({3: 5, 100: 1}, max_buckets=2, max_bucket=16)
    assert max(capped) <= 16

    assert propose_buckets(hist, max_buckets=3) == \
        propose_buckets(hist, max_buckets=3)
    assert 0.0 <= ladder_waste(got, hist) < 1.0

    with pytest.raises(ValueError):
        propose_buckets({})


def test_serve_engine_bucket_advisory():
    from timm_tpu.serve.engine import InferenceEngine

    eng = InferenceEngine(buckets=(2, 16))
    assert eng.bucket_advisory() is None            # no traffic yet
    eng.stats['request_sizes'].update({1: 50, 2: 30, 16: 1})
    adv = eng.bucket_advisory()
    assert adv is not None
    assert adv['proposed_waste'] < adv['current_waste']
    assert adv['requests'] == 81
    assert max(adv['proposed']) >= 16


# ---- probe integration / small fix ------------------------------------------

def test_cost_analysis_logs_config_name_once(caplog):
    from timm_tpu.perfbudget.probe import _COST_WARNED, _cost_analysis

    class Boom:
        def cost_analysis(self):
            raise RuntimeError('backend says no')

    _COST_WARNED.discard('boomcfg')
    with caplog.at_level(logging.WARNING, logger='timm_tpu.perfbudget.probe'):
        assert _cost_analysis(Boom(), 'boomcfg') == {}
        assert _cost_analysis(Boom(), 'boomcfg') == {}
    msgs = [r.getMessage() for r in caplog.records if 'boomcfg' in r.getMessage()]
    assert len(msgs) == 1, 'the warning must fire exactly once per config'
    assert 'RuntimeError' in msgs[0] and 'backend says no' in msgs[0]


# ---- user surfaces ----------------------------------------------------------

def test_apply_to_args_and_json_surface():
    from timm_tpu.autotune import apply_to_args, autotune, format_table, to_json

    res = autotune('test_vit', MODEL_KW, global_batch=64, probe_anchor=False,
                   correction=1.0)
    ns = argparse.Namespace(fsdp=0, tp=0, batch_size=8, grad_accum_steps=8,
                            block_scan=False, grad_checkpointing=False)
    notes = apply_to_args(ns, res)
    w = res.winner
    assert ns.batch_size * ns.grad_accum_steps == 64
    assert ns.fsdp == (w.fsdp if w.fsdp > 1 else 0)
    assert ns.tp == (w.tp if w.tp > 1 else 0)
    assert ns.block_scan == w.block_scan
    assert any('batch_size' in n or 'fsdp' in n for n in notes)

    table = format_table(res)
    assert 'winner:' in table and w.flags() in table

    doc = to_json(res)
    json.dumps(doc)   # must be serializable as-is
    assert doc['schema'] == 'autotune/v1'
    assert doc['winner_flags'] == w.flags()
    assert doc['ranked'][0]['rank'] == 1
    assert doc['global_batch'] == 64


def test_module_cli_emits_json(capsys):
    from timm_tpu.autotune.__main__ import main

    rc = main(['--model', 'test_vit',
               '--model-kwargs', json.dumps(MODEL_KW),
               '--global-batch', '64', '--devices', '8', '--top', '3'])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc['schema'] == 'autotune/v1'
    assert doc['n_devices'] == 8 and len(doc['ranked']) == 3
    assert doc['tier'] == 'analytic'

    rc = main(['--model', 'test_vit',
               '--model-kwargs', json.dumps(MODEL_KW),
               '--global-batch', '30', '--devices', '8'])
    assert rc == 1
    doc = json.loads(capsys.readouterr().out)
    assert 'error' in doc and doc['rejections']


@pytest.mark.slow
def test_train_autotune_subprocess(tmp_path):
    """End-to-end acceptance drill: `train.py --autotune` on the 8-device CPU
    topology enumerates, ranks, applies the winner, and completes an epoch.
    Tier-1 covers the same surface in-process (apply_to_args + CLI tests)."""
    cmd = [
        sys.executable, os.path.join(REPO, 'train.py'),
        '--synthetic-data', '--model', 'test_vit', '--img-size', '32',
        '-b', '8', '--grad-accum-steps', '2', '--synthetic-len', '32',
        '--epochs', '1', '--opt', 'sgd', '--lr', '0.05', '--sched', 'cosine',
        '--warmup-epochs', '0', '--workers', '1', '--log-interval', '50',
        '--autotune', '--output', str(tmp_path), '--experiment', 'at',
    ]
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               XLA_FLAGS='--xla_force_host_platform_device_count=8')
    r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       cwd=REPO, timeout=420)
    assert r.returncode == 0, r.stderr[-3000:]
    assert '[autotune] winner:' in r.stderr, r.stderr[-3000:]
    assert '[autotune] applied' in r.stderr, r.stderr[-3000:]
