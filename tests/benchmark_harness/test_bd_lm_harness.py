"""The block-diffusion language-model cell's part of the benchmark (`bd_lm_train_runner.py`, `bd_lm_flops.py`,
`bd_lm_readers.py`, the configuration and the cell ISSUE 37 brings), at `sdar_moe_toy` size on the CPU. One file,
like its neighbours.

Two readings are this family's own (`bd_lm_readers.READERS`); the five of `lm_readers.READERS` /
`swa_lm_readers.READERS` whose scopes and parts its step shares list its cell and read its record with no new
code. The toy manifest below lists its cell wherever the real cell is listed. What the manifest must have is held
as a subset of what it has: a later PR adds cells and metrics and may not edit this file.
"""
import json
import math
import os
import shutil
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import bd_lm_flops, bd_lm_readers, bd_lm_train_runner, check, device_scopes, lm_readers, swa_lm_readers  # noqa: E402
from benchmarks.harness.manifest import BENCH_DIR, Manifest, load_json, runner_module  # noqa: E402

CELL, SWA_CELL, GLM_CELL, CONFIG = ('sdar_30b_a3b_ep8_train_bd4_8k', 'smallthinker_21b_ep8_train_16k', 'glm47_flash_ep8_train_8k',
                                    'sdar_30b_a3b_ep8')
OWN = ['attn_bd_core_mfu.train', 'attn_bd_block_fill.train']                                # `bd_lm_readers.READERS`'
SHARED = ['moe_route_device_ms.train', 'moe_device_ms.train', 'moe_experts_mfu.train', 'attn_device_ms.train', 'attn_proj_mfu.train']
NOT_ITS = {'input_prepare_ms.train', 'input_decode_busy_share.train', 'mla_device_ms.train', 'mla_core_mfu.train',
           'attn_full_core_mfu.train', 'attn_window_core_mfu.train', 'attn_window_block_fill.train'}
TOY_SIZES = dict(vocab_held=256, hidden_size=64, num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                 moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2, experts_held=2, expert_offset=0,
                 rope_theta=1e6, rms_norm_eps=1e-6, block_length=4, mask_token_id=255, noise_eps=1e-3)
# float32 on both sides: summation order only (Adam's division makes 1e-4 of a change norm; a loss weighted by 1/p
# carries 1/p of it); float8 operands move every number by 1e-2 and more
TOY_LIMITS = {'loss_gap': 1e-3, 'first_grad_norm_gap': 2e-4, 'param_change_norm_gap': 2e-3, 'ema_change_norm_gap': 2e-3}
SWA_RECORD = {'runner': 'train', 'steps': 3, 'device_kind': 'TPU v5 lite', 'lm': {'seq_len': 32, 'sequences': 8, 'expert_layers': 4},
              'sizes': dict(vocab_held=256, hidden_size=64, num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
                            head_dim=16, moe_ffn_hidden_size=32, moe_num_primary_experts=8, experts_held=2,
                            sliding_window_layout=[0, 1, 1, 1], sliding_window_size=8),
              'counters': {'moe.local_slots': [700, 800], 'attn.full_blocks': [80, 80], 'attn.window_blocks': [168, 168]},
              'needed_macs': {'attn_core_full': 4e9, 'attn_proj': 2e9, 'moe_experts': 1e9},
              'trace': {'busy_s': 0.5, 'window_s': 1.0, 'idle_share': 0.5, 'work': 5, 'scopes': {
                  'scope_s': {'swa.attn.core_full': 0.2, 'swa.attn.proj': 0.05, 'glm.moe.route': 0.03, 'glm.moe.experts': 0.04},
                  'busy_s': 0.5, 'unscoped': []}}}
GLM_RECORD = {'runner': 'train', 'steps': 3, 'device_kind': 'TPU v5 lite', 'lm': {'seq_len': 64, 'sequences': 8},
              'sizes': dict(vocab_held=256, hidden_size=64, num_hidden_layers=3, num_attention_heads=4, q_lora_rank=24),
              'counters': {'moe.local_slots': [700, 800]}, 'needed_macs': {'mla_core': 4e9, 'moe_experts': 1e9},
              'trace': {'busy_s': 0.5, 'window_s': 1.0, 'idle_share': 0.5, 'work': 5, 'scopes': {
                  'scope_s': {'glm.mla.core': 0.2, 'glm.moe.route': 0.03, 'glm.moe.experts': 0.04}, 'busy_s': 0.5, 'unscoped': []}}}


def test_the_manifest_has_the_configuration_the_cell_and_its_readers_entries():
    m = Manifest()
    names = [x['name'] for x in m.data['per_layer']]
    assert set(bd_lm_readers.READERS) == set(OWN) and [n for n in names[36:] if n in OWN] == OWN
    assert [w['name'] for w in m.data['workloads']][4] == CELL and [c['name'] for c in m.data['configs']][4] == CONFIG
    assert m.data['run_seconds'] == 20 and m.cells[CELL]['traffic'] == 'train_token_stream' and len(m.cells[CELL]['why']) <= 200
    # the cell is listed, last, under train_img_per_s and the 24 per-layer lists ISSUE 37 names; the five metrics
    # without a list reach it by themselves
    listed = [x['name'] for x in m.data['end_to_end'] + m.data['per_layer'][:36] if x.get('workloads', [None])[-1] == CELL]
    assert len(listed) == 1 + 24 and set(SHARED) <= set(listed) and not NOT_ITS & set(listed)
    for metric in m.data['end_to_end'] + m.data['per_layer'][:36]:
        cells = metric.get('workloads', [])
        assert (CELL in cells) == (metric['name'] in listed) and (SWA_CELL in cells or CELL not in cells)
    its = m.metrics_of(CELL, 'per_layer')
    assert not NOT_ITS & set(its) and set(OWN + SHARED) | {'step_mfu.train', 'warm_compile_misses', 'setup_compile_s'} <= set(its)
    assert m.metrics_of(CELL, 'end_to_end') == ['train_img_per_s', 'setup_s'] and len(its) == 24 + 5 + 2
    for name, r in bd_lm_readers.READERS.items():           # what their entries say
        entry = bd_lm_readers.entry(name, [CELL])
        assert m.per_layer[name] == entry == {'name': name, 'unit': '%', 'better': 'higher', 'source': r.source, 'layer': 'attention',
                                              'moves': 'train_img_per_s', 'workloads': [CELL]}
        assert m.reader(name)({}) is None
    assert [bd_lm_readers.READERS[n].source for n in OWN] == ['device_trace', 'program_counter']
    cell, config = m.cell(CELL), m.config(CONFIG)
    assert cell['runner'] == 'bd_lm_train' and cell['chips'] == 1
    stream = cell['traffic']['token_stream']
    assert (stream['tokens'], stream['validation_tokens'], stream['data_seed']) == (8_388_608, 32768, 20260930)
    assert cell['traffic']['warmup_steps'] == 6 and stream['tokens'] // 8192 == 1024
    assert {'source', 'published', 'deployment', 'reduced', 'reduced_why', 'assumed', 'precision', 'sizes', 'limits',
            'limits_why'} <= set(config)
    assert config['reduced'] == ['num_hidden_layers', 'num_experts', 'vocab_size'] == list(config['reduced_why']) == m.data['configs'][4]['reduced']
    assert config['train_args'] == '-b 1 --amp --opt adamw --opt-betas 0.9 0.95 --weight-decay 0.1 --clip-grad 1.0 ' \
                                   '--grad-checkpointing --dataset tokens --seq-len 8192'.split()
    assert {'block_length', 'forward_process', 'loss_weight', 'targets', 'qk_norm', 'rotary_pairing', 'router_loss', 'mask_token',
            'recipe'} <= set(config['assumed'])
    path = '/opt/skills/guides/model-configs/architectures.jsonl'
    for row in [json.loads(line) for line in open(path)] if os.path.exists(path) else []:
        if row['name'] == 'SDAR-30B-A3B-Chat':               # every published number under its key, but the three reduced
            assert config['source'] == row['source_url'] == m.data['configs'][4]['source']
            off = {k for k, v in row['config'].items() if config.get(k, 'missing') != v}
            assert off == set(config['reduced']) == set(config['published']) and all(
                config['published'][k] == row['config'][k] for k in off)
    sizes = config['sizes']
    assert (sizes['num_hidden_layers'], sizes['experts_held'], sizes['vocab_held']) == (6, 16, 18992) == (
        config['num_hidden_layers'], config['num_experts'], config['vocab_size'])
    assert all(sizes[k] == config[k] for k in ('hidden_size', 'num_attention_heads', 'num_key_value_heads', 'head_dim',
                                               'moe_intermediate_size', 'num_experts_per_tok', 'rope_theta', 'rms_norm_eps'))
    assert (sizes['num_experts'], sizes['block_length'], sizes['mask_token_id'], sizes['noise_eps']) == (128, 4, 18991, 1e-3)
    assert set(config['limits']['bd_lm_train']) == set(TOY_LIMITS) and set(config['limits_why']) >= set(TOY_LIMITS) | {'route_agreement_min'}
    assert 0.9 <= config['limits_lm']['route_agreement_min'] < 1.0 and len(config['source']) <= 200
    # the held parameters, from the reference's own shapes: ISSUE 37's arithmetic
    from benchmarks.reference import sdar_moe
    assert sum(math.prod(shape) for shape, _ in sdar_moe.init_spec(sizes).values()) == 645_623_296


@pytest.mark.parametrize('length,block', [(4, 4), (8, 1), (8, 2), (12, 4), (32, 4), (32, 32), (48, 16)])
def test_the_pair_counts_are_the_masks_own(length, block):
    """Against the mask written out rule by rule with loops, here and not imported."""
    seen = np.zeros((2 * length, 2 * length), bool)
    for i in range(length):
        for j in range(length):
            seen[i, j] = j // block == i // block
            seen[i, length + j] = j // block < i // block
            seen[length + i, length + j] = j // block <= i // block
    assert bd_lm_flops.mask_pairs(length, block) == int(seen.sum()) == length * length + length * block
    assert bd_lm_flops.noised_pairs(length, block) == int(seen[:length].sum())


def test_needed_operations_are_the_issues_table():
    sizes = Manifest().config(CONFIG)['sizes']
    assert bd_lm_flops.mask_pairs(8192, 4) == 67_141_632 and bd_lm_flops.noised_pairs(8192, 4) == 33_570_816
    macs = bd_lm_flops.forward_macs(sizes, 8192, 1, local_slots=90_112)          # even routing: 2 T x 8 x 16 / 128 a layer
    assert macs['attn_core_bd'] == 369_278_976 * 32 * 256 == pytest.approx(3.025e12, rel=1e-3)
    assert macs['attn_proj'] == 5 * 16384 * 18_874_368 + 8192 * 18_874_368 + 8192 * 2_097_152 == pytest.approx(1.718e12, rel=1e-3)
    assert macs['moe_experts'] == 90_112 * 4_718_592 == pytest.approx(4.25e11, rel=2e-3)
    assert macs['head'] == 8192 * 2048 * 18992 == pytest.approx(3.186e11, rel=1e-3)
    assert macs['moe_route'] == 90_112 * 262_144 == pytest.approx(2.36e10, rel=2e-3)
    total = sum(macs.values())
    assert total == pytest.approx(5.51e12, rel=1e-3) and bd_lm_flops.train_flops(macs) == pytest.approx(33.1e12, rel=2e-3)
    assert [round(100 * macs[k] / total, 1) for k in ('attn_core_bd', 'attn_proj', 'moe_experts', 'head', 'moe_route')] == [54.9, 31.2, 7.7, 5.8, 0.4]
    # a causal core over the 2 L rows would multiply 2.0 x the pairs; the 80 tiles of 1024 hold the needed ones at 80.0 %
    assert 16384 * 16385 // 2 / 67_141_632 == pytest.approx(2.0, abs=0.001)
    run = {'lm': {'seq_len': 8192, 'sequences': 1}, 'sizes': sizes, 'counters': {'attn.bd_blocks': [5 * 80 + 44] * 3}}
    assert bd_lm_readers.block_side(run) == 1024 and bd_lm_readers.block_fill(run) == pytest.approx(100 * 369_278_976 / (444 * 1024 * 1024))
    assert bd_lm_readers.block_fill(run) == pytest.approx(79.3, abs=0.05)
    assert bd_lm_readers.block_side(dict(run, counters={'attn.bd_blocks': [443]})) is None       # no whole number of blocks gives it


@pytest.fixture(scope='module')
def toy(tmp_path_factory):
    """A copy of the benchmark's data with a toy configuration and cell of the new runner added as files."""
    tmp = tmp_path_factory.mktemp('toybd')
    bench = tmp / 'benchmarks'
    for d in ('configs', 'workloads', 'layer_metrics'):
        shutil.copytree(os.path.join(BENCH_DIR, d), bench / d)
    man = load_json(os.path.join(ROOT, 'BENCHMARK.json'))
    (bench / 'configs' / 'toy_bd.json').write_text(json.dumps({
        'name': 'toy_bd', 'source': 'test', 'model': 'sdar_moe_toy', 'reference': 'sdar_moe', 'sizes': TOY_SIZES,
        'recipe': {'clip_grad': 1.0, 'weight_decay': 0.1, 'betas': [0.9, 0.95]},
        'train_args': ['-b', '8', '--opt', 'adamw', '--opt-betas', '0.9', '0.95', '--weight-decay', '0.1', '--clip-grad', '1.0',
                       '--grad-checkpointing', '--dataset', 'tokens', '--seq-len', '32'],
        'reduced': [], 'reference_block_q': 8, 'limits': {'bd_lm_train': TOY_LIMITS}, 'limits_lm': {'route_agreement_min': 0.99}}))
    (bench / 'workloads' / 'toy_bd_train.json').write_text(json.dumps({
        'config': 'toy_bd', 'runner': 'bd_lm_train', 'chips': 1,
        'traffic': {'warmup_steps': 3, 'token_stream': {'name': 'toy', 'data_seed': 1, 'tokens': 32 * 8 * 400,
                                                        'validation_tokens': 32 * 8}}}))
    man['configs'].append({'name': 'toy_bd', 'source': 'test', 'file': 'benchmarks/configs/toy_bd.json', 'reduced': [], 'why': 'test'})
    man['workloads'].append({'name': 'toy_bd_train', 'config': 'toy_bd', 'traffic': 'toy_tokens', 'chips': 1, 'why': 'test'})
    for metric in man['end_to_end'] + man['per_layer']:
        if CELL in metric.get('workloads', ()):
            metric['workloads'].append('toy_bd_train')
    (tmp / 'BENCHMARK.json').write_text(json.dumps(man))
    return Manifest(bench_dir=str(bench), manifest_path=str(tmp / 'BENCHMARK.json')), str(tmp / 'scratch')


def _run(toy, seconds, **kw):
    m, scratch = toy
    cell = m.cell('toy_bd_train')
    lines = []
    record = runner_module(cell['runner']).run(cell, m.config(cell['config']), seed=2 ** 31 + 11, seconds=seconds, trace=False,
                                               process_start=time.perf_counter(), scratch=scratch, log=lines.append, **kw)
    return record, lines


@pytest.fixture(scope='module')
def sound(toy):
    return _run(toy, 0.4, control_precision='float8')


def test_the_new_runner_runs_a_cell_added_by_files_and_prints_the_contracts_line(toy, sound):
    from benchmarks import run as bench_run
    record, lines = sound
    assert record['correct'] and record['failed'] == 0 and record['attempted'] > 0 and record['compiles_in_window'] == 0
    assert record['runner'] == 'train' and record['batch_size'] == 8 and record['lm']['seq_len'] == 32
    compared = {l.split()[1].rstrip(':') for l in lines if l.startswith('check ')}
    assert {'loss_gap_step3', 'first_grad_norm_gap', 'param_change_norm_gap', 'feed_repeated_rows', 'feed_targets_off',
            'feed_negative_ids', 'noise_ids_off', 'noise_clean_is_mask', 'noise_share_off', 'noise_repeated', 'noise_count_off',
            'moe_dropped_slots', 'step_counters_missing', 'route_agreement', 'first_masked_nll', 'compiles_in_window'} <= compared
    assert 'ema_change_norm_gap' not in compared and record['numbers']['route_agreement'] == 1.0
    steps = record['steps']
    assert all(len(record['counters'][k]) == steps for k in ('moe.local_slots', 'moe.load_max', 'moe.dropped_slots', 'lm.tokens',
                                                             'attn.bd_blocks', 'lm.noised_masked', 'lm.masked_nll'))
    assert set(record['counters']['lm.tokens']) == {8 * 32} and set(record['counters']['moe.dropped_slots']) == {0}
    assert set(record['counters']['attn.bd_blocks']) == {8 * (24 + 24 + 14)}
    assert len({tuple(p) for p in record['followed']['p']}) == 3 and all(1e-3 <= x < 1 for p in record['followed']['p'] for x in p)
    device = {'platform': 'cpu', 'kind': 'cpu', 'count': 1}
    plain = bench_run.result_line(toy[0], 'toy_bd_train', record, device, trace=False)
    assert set(plain['metrics']) == {'train_img_per_s', 'setup_s'} and plain['correct']
    # the traced line: the readers the other cells have read this run, the new ones read its counters and scopes
    scopes = {'scope_s': {'swa.attn.core_bd': 0.2, 'swa.attn.proj': 0.05, 'glm.moe.experts': 0.04, 'glm.moe.route': 0.03,
                          'glm.head_loss': 0.05, 'glm.embed': 0.001},
              'busy_s': 0.5, 'unscoped': [['fusion', 0.05]]}
    traced = dict(record, device_kind='TPU v5 lite', trace={
        'busy_s': 0.5, 'window_s': 1.0, 'idle_share': 0.5, 'work': 5, 'idle_total_s': 0.5, 'scopes': scopes,
        'breakdown': {'device_ops': [], 'idle_gaps': []}})
    line = json.loads(json.dumps(bench_run.result_line(toy[0], 'toy_bd_train', traced, device, trace=True)))
    got = {k: v['value'] for k, v in line['metrics'].items()}
    assert set(OWN + SHARED) | {'step_mfu.train'} <= set(got) and not NOT_ITS & set(got)
    assert {'step_device_ms.train', 'step_wall_ms.train', 'dispatch_host_ms.train', 'input_host_ms.train', 'step_call_ms.train',
            'device_idle_share.train', 'input_batch_wait_ms.train', 'loop_bookkeeping_ms.train', 'setup_compile_s',
            'warm_compile_misses', 'setup_model_build_s', 'setup_data_build_s', 'setup_step_program_s'} <= set(got)
    assert got['attn_device_ms.train'] == pytest.approx(50.0) and got['moe_device_ms.train'] == pytest.approx(14.0)
    slots = sum(record['counters']['moe.local_slots']) / steps
    macs = bd_lm_flops.forward_macs(TOY_SIZES, 32, 8, slots)
    assert record['needed_macs'] == macs and record['needed_step_flops'] == bd_lm_flops.train_flops(macs)
    assert record['lm']['expert_layers'] == 3 and record == dict(record, **bd_lm_train_runner.needed_work(toy[0].config('toy_bd'), record))
    assert set(record['checks']) == compared and all(c['ok'] for c in record['checks'].values())
    assert got['step_mfu.train'] == pytest.approx(100 * bd_lm_flops.train_flops(macs) / 0.1 / 197e12)
    assert got['attn_bd_core_mfu.train'] == pytest.approx(100 * 6 * macs['attn_core_bd'] / 0.04 / 197e12)
    assert got['attn_proj_mfu.train'] == pytest.approx(100 * 6 * macs['attn_proj'] / 0.01 / 197e12)
    assert got['moe_experts_mfu.train'] == pytest.approx(100 * 6 * macs['moe_experts'] / 0.008 / 197e12)
    # tiles of 8 x 8 (from the cores' own count): 2 x 1152 + 576 needed pairs a sequence in 62 tiles of 64
    assert bd_lm_readers.block_side(traced) == pytest.approx(8.0) and bd_lm_flops.mask_pairs(32, 4) == 1152
    assert got['attn_bd_block_fill.train'] == pytest.approx(100 * (2 * 1152 + 576) / (62 * 64))
    table = device_scopes.scope_table(traced, bd_lm_readers.SCOPE_PARTS)
    assert any(l.startswith('device scopes cover 74.2 %') for l in table)
    assert any(l.startswith('device scope swa.attn.core_bd: 40.00 ms a step, 40.0 % of busy, ') for l in table)
    said = {l.split()[1].rstrip(':'): float(l.split()[2]) for l in lm_readers.lines(traced)}
    assert said['moe_slots_per_expert.train'] == pytest.approx(slots / (2 * 3), rel=1e-5)
    assert any(l.startswith('memory_peak_bytes: ') for l in lines) and any('clean tokens/s' in l for l in lines)
    assert set(bd_lm_readers.SCOPE_PARTS) <= bd_lm_readers.declared_scopes()


@pytest.mark.parametrize('name', OWN)
def test_a_new_reader_returns_none_where_there_is_nothing_to_read(name):
    """A parent without the scope and the counter, an image cell's run, an empty record, the GLM cell's and the
    SmallThinker cell's records: no value, no raise."""
    read = bd_lm_readers.READERS[name].read
    assert read({}) is None and read(GLM_RECORD) is None and read(SWA_RECORD) is None
    assert read({'runner': 'train', 'steps': 3, 'sizes': {'embed_dim': 768}, 'device_kind': 'TPU v5 lite',
                 'trace': {'busy_s': 0.5, 'window_s': 1.0, 'idle_share': 0.5, 'work': 5}}) is None
    # this family's sizes and nothing measured: still nothing
    assert read({'runner': 'train', 'sizes': TOY_SIZES, 'device_kind': 'TPU v5 lite', 'lm': {'seq_len': 32, 'sequences': 8}}) is None
    # and the accepted readings this cell shares read the other families' records as they did
    assert swa_lm_readers.READERS['attn_proj_mfu.train'].read(SWA_RECORD) == pytest.approx(100 * 6 * 2e9 / 0.01 / 197e12)
    assert lm_readers.READERS['moe_route_device_ms.train'].read(GLM_RECORD) == pytest.approx(6.0)


def test_the_float8_control_is_not_correct(toy, sound):
    record, _ = sound
    limits = toy[0].config('toy_bd')['limits']['bd_lm_train']
    numbers = lambda d: {k: (v, '') for k, v in d.items() if k != 'route_agreement'}  # noqa: E731
    assert check.judge(numbers(record['numbers']), limits, out=lambda s: None)
    assert not check.judge(numbers(record['control_numbers']), limits, out=lambda s: None)
    assert record['control_correct'] is False and record['control_numbers']['first_grad_norm_gap'] > 10 * limits['first_grad_norm_gap']
    assert any(l.startswith('control float8 check ') and l.split('(')[0].rstrip().endswith('OVER') for l in sound[1])


def test_a_step_that_returns_its_state_unchanged_is_not_correct(toy):
    import jax.numpy as jnp
    stuck = lambda task, batch, lr, step=0: {'loss': jnp.float32(5.5), 'grad_norm': jnp.float32(1.0)}  # noqa: E731
    record, lines = _run(toy, 0.2, inner_step=stuck)
    over = {l.split()[1].rstrip(':') for l in lines if l.startswith('check ') and l.split('(')[0].rstrip().endswith('OVER')}
    assert not record['correct'] and {'param_change_norm_gap', 'first_grad_norm_gap', 'step_counters_missing', 'noise_count_off'} <= over
    assert record['numbers']['param_change_norm_gap'] == pytest.approx(1.0) and 'attn.bd_blocks' not in record['counters']


def test_a_step_whose_noise_repeats_is_not_correct(toy):
    """A task that never counts its draws on noises every step alike: the followed steps' p and masks repeat."""
    import jax.numpy as jnp
    from timm_tpu.task import TrainingTask      # the watcher has its wrapper on the subclasses' `train_step`

    def frozen(task, batch, lr, step=0):
        count = int(task.model.noise_count[...])                # the array itself is donated to the step
        metrics = TrainingTask.train_step(task, batch, lr, step)
        task.model.noise_count[...] = jnp.uint32(count)
        return metrics
    record, lines = _run(toy, 0.2, inner_step=frozen)
    over = {l.split()[1].rstrip(':') for l in lines if l.startswith('check ') and l.split('(')[0].rstrip().endswith('OVER')}
    assert not record['correct'] and over == {'noise_repeated'} and record['checks']['noise_repeated']['value'] == 2 * 16      # 24 sequences, 8 p and 8 masks


def test_the_noise_checks_hold_what_the_objective_says():
    rng = np.random.default_rng(0)
    clean = rng.integers(0, 255, (2, 400)).astype(np.int32)
    p = np.asarray([0.3, 0.7], np.float32)
    masked = rng.random((2, 400)) < p[:, None]
    step, drawn = {'input': clean}, {'noised': np.where(masked, 255, clean), 'masked': masked, 'p': p}
    sound = bd_lm_train_runner.noise_numbers([step], [drawn], [int(masked.sum())], 255)
    assert all(value == limit for value, limit, _ in sound.values()) and len(sound) == 5
    off = lambda **kw: {k for k, (value, limit, _) in bd_lm_train_runner.noise_numbers(  # noqa: E731
        [dict(step, **kw.pop('step', {}))], [dict(drawn, **kw)], [kw.pop('count', int(masked.sum()))], 255).items() if value != limit}
    assert off(noised=np.where(masked, 254, clean)) == {'noise_ids_off'}
    assert off(step={'input': np.where(np.arange(400) == 3, 255, clean)}) >= {'noise_clean_is_mask'}
    assert off(p=np.asarray([0.6, 0.7], np.float32)) == {'noise_share_off'} and off(p=np.asarray([0.3, 0.3], np.float32)) >= {'noise_repeated'}
    assert bd_lm_train_runner.noise_numbers([step], [drawn], [3], 255)['noise_count_off'][0] == 1


def test_device_time_is_reduced_by_the_new_scope_too():
    names = bd_lm_readers.declared_scopes()
    assert names >= device_scopes.declared_scopes() >= {'swa.attn.core_bd'} and 'swa.attn.proj' in names
    of = lambda op: device_scopes.scope_of(op, names)  # noqa: E731
    assert of('jit(train_step)/transpose(jvp(checkpoint))/swa.attn.core_bd/vmap(jit(_splash_attention))/pallas_call') == 'swa.attn.core_bd'
    # a real step program's compiled text names the scopes (the CPU's here; the chip's in a traced run)
    import jax
    import timm_tpu
    from flax import nnx
    model = timm_tpu.create_model('sdar_moe_toy', seed=0)
    graphdef, state = nnx.split(model)
    ids = jax.numpy.zeros((1, 32), 'int32')
    text = jax.jit(lambda st: nnx.merge(graphdef, st)(ids, ids)).lower(state).compile().as_text()
    assert {'glm.embed', 'swa.attn.proj', 'swa.attn.core_bd', 'glm.moe.route'} <= set(device_scopes.instruction_scopes(text, names).values())
