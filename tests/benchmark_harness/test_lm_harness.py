"""The language-model cell's part of the benchmark (`lm_train_runner.py`,
`lm_traffic.py`, `lm_flops.py`, `device_scopes.py`, the readers ISSUE 26
brings), at `glm4_moe_lite_toy` size on the CPU. One file, like its neighbours.

Five of the readings are metrics of `BENCHMARK.json` since PR 35 (each its entry
and its file under `layer_metrics/`), the whole step's share of the peak is
`step_mfu.train`'s, and two stay free text (`lm_readers.PRINTED`). The toy
manifest below lists its cell wherever the GLM cell is listed, and
`result_line` prints what the real cell's line will carry. What the manifest
must have is held as a subset of what it has: a later PR adds cells and
metrics, of this family or another, and may not edit this file.
"""
import json
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

from benchmarks.harness import check, device_scopes, lm_flops, lm_readers, lm_traffic  # noqa: E402
from benchmarks.harness.manifest import BENCH_DIR, Manifest, load_json, runner_module  # noqa: E402

CELL = 'glm47_flash_ep8_train_8k'
METRICS = ['moe_route_device_ms.train', 'moe_device_ms.train', 'moe_experts_mfu.train', 'mla_device_ms.train',
           'mla_core_mfu.train']                             # in the manifest's order
COUNTED = list(lm_readers.PRINTED)
NEW = METRICS + COUNTED
NOT_ITS = {'input_prepare_ms.train', 'input_decode_busy_share.train'}     # the image feed's
TOY_SIZES = dict(vocab_held=256, hidden_size=64, num_hidden_layers=3, num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
                 qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16, intermediate_size=160, moe_intermediate_size=32,
                 n_routed_experts=8, num_experts_per_tok=2, experts_held=2, expert_offset=0, n_shared_experts=1,
                 routed_scaling_factor=1.8, first_k_dense_replace=1, num_nextn_predict_layers=1, rope_theta=1e6,
                 rms_norm_eps=1e-5, mtp_loss_weight=0.3)
# float32 on both sides: summation order only (1e-6 seen; Adam's division makes 1e-4 of a change norm); bfloat16
# operands move every number by 1e-3 and more
TOY_LIMITS = {'loss_gap': 2e-4, 'first_grad_norm_gap': 2e-4, 'param_change_norm_gap': 2e-3, 'ema_change_norm_gap': 2e-3}


def test_the_manifest_has_the_configuration_the_cell_and_its_readers_entries():
    m = Manifest()
    names = [x['name'] for x in m.data['per_layer']]
    assert set(lm_readers.READERS) == set(NEW) and 'lm_step_mfu.train' not in names       # one share, one name
    for entry in m.data['per_layer']:                       # what the pinned test says of eighteen, of all
        assert callable(m.reader(entry['name']))            # LAYER, UNIT, MOVES of the file agree with the entry
        assert 'workloads' in entry or entry['moves'] != 'train_img_per_s'
    assert [n for n in names[26:] if n in lm_readers.READERS] == METRICS and not set(COUNTED) & set(names)
    for name, r in lm_readers.READERS.items():              # what their entries say
        cells = [CELL] if name.startswith('mla_') else [CELL, 'smallthinker_21b_ep8_train_16k']
        assert lm_readers.entry(name, cells) == {
            'name': name, 'unit': r.unit, 'better': r.better, 'source': 'program_counter' if name in COUNTED else 'device_trace',
            'layer': r.layer, 'moves': 'train_img_per_s', 'workloads': cells}
        have = m.per_layer.get(name, {'workloads': cells})  # the entry says what the table says; more cells may list it
        assert name in COUNTED or (dict(have, workloads=cells) == lm_readers.entry(name, cells) and set(cells) <= set(have['workloads']))
        assert r.layer in ('attention', 'experts') and r.unit in ('%', 'ms', 'count', 'ratio') and r.better in ('lower', 'higher')
        assert (r.better == 'higher') == (name.endswith('mfu.train') or name == 'moe_slots_per_expert.train')
    listed = {x['name'] for x in m.data['per_layer'] if CELL in x.get('workloads', ())}
    assert {n for n in names[:26] if n.endswith('.train')} - NOT_ITS | set(METRICS) <= listed and not NOT_ITS & listed
    assert m.metrics_of(CELL, 'end_to_end') == ['train_img_per_s', 'setup_s']
    cell, config = m.cell(CELL), m.config('glm47_flash_ep8')
    assert cell['runner'] == 'lm_train' and cell['chips'] == 1 and m.cells[CELL]['traffic'] == 'train_token_stream'
    assert cell['traffic']['token_stream']['tokens'] == 8_388_608 and cell['traffic']['warmup_steps'] == 6
    catalog = [json.loads(l) for l in open('/opt/skills/guides/model-configs/architectures.jsonl')] \
        if os.path.exists('/opt/skills/guides/model-configs/architectures.jsonl') else []
    for row in catalog:
        if row['name'] == 'GLM-4.7-Flash':                   # every published number under its key, but the three reduced
            assert config['source'] == row['source_url']
            off = {k for k, v in row['config'].items() if config.get(k, 'missing') != v}
            assert off == set(config['reduced']) == set(config['published']) and all(
                config['published'][k] == row['config'][k] for k in off)
    sizes = config['sizes']
    assert (sizes['num_hidden_layers'], sizes['experts_held'], sizes['vocab_held']) == (5, 8, 19360) == (
        config['num_hidden_layers'], config['n_routed_experts'], config['vocab_size'])
    assert all(sizes[k] == config[k] for k in ('hidden_size', 'q_lora_rank', 'kv_lora_rank', 'qk_nope_head_dim',
                                               'qk_rope_head_dim', 'v_head_dim', 'intermediate_size',
                                               'moe_intermediate_size', 'num_experts_per_tok', 'routed_scaling_factor'))
    assert sizes['n_routed_experts'] == 64 and len(config['source']) <= 200 and set(config['limits_why']) >= set(config['limits']['lm_train'])


def test_needed_operations_are_the_issues_arithmetic():
    sizes = Manifest().config('glm47_flash_ep8')['sizes']
    macs = lm_flops.forward_macs(sizes, 8192, 2, local_slots=16384 * 5 * 4 * 8 / 64)       # even routing
    per_token = {k: v / 16384 for k, v in macs.items()}
    assert per_token['mla_proj'] / 6 == pytest.approx(21.76e6, rel=1e-3) and per_token['dense_ffn'] == pytest.approx(62.91e6, rel=1e-3)
    assert per_token['mla_core'] == pytest.approx(251.7e6, rel=1e-3)
    assert sum(per_token.values()) == pytest.approx(604e6, rel=5e-3)                       # ISSUE 26: 604M MACs a token
    assert lm_flops.train_flops(macs) == pytest.approx(59e12, rel=0.01) and lm_flops.train_flops(10) == 60
    assert per_token['moe_experts'] / sum(per_token.values()) == pytest.approx(0.039, abs=0.002)
    assert lm_flops.forward_macs(sizes, 8192, 2, 0)['moe_experts'] == 0


def test_the_token_stream_is_seeded_in_range_and_written_once(tmp_path):
    mix = {'name': 'toy', 'data_seed': 5, 'tokens': 4096, 'validation_tokens': 512}
    roots = [lm_traffic.write_token_stream(str(tmp_path / d), mix, 300) for d in ('a', 'b')]
    ids = np.fromfile(os.path.join(roots[0], 'train.bin'), '<i4')
    assert ids.shape == (4096,) and ids.min() >= 0 and 290 < ids.max() < 300
    assert (ids == np.fromfile(os.path.join(roots[1], 'train.bin'), '<i4')).all()
    assert np.fromfile(os.path.join(roots[0], 'validation.bin'), '<i4').shape == (512,)
    stamp = os.stat(os.path.join(roots[0], 'train.bin')).st_mtime_ns
    assert lm_traffic.write_token_stream(roots[0], mix, 300) == roots[0]
    assert os.stat(os.path.join(roots[0], 'train.bin')).st_mtime_ns == stamp
    other = lm_traffic.write_token_stream(str(tmp_path / 'c'), dict(mix, data_seed=6), 300)
    assert (ids != np.fromfile(os.path.join(other, 'train.bin'), '<i4')).any()


@pytest.fixture(scope='module')
def toy(tmp_path_factory):
    """A copy of the benchmark's data with a toy configuration and cell of the new runner added as files."""
    tmp = tmp_path_factory.mktemp('toylm')
    bench = tmp / 'benchmarks'
    for d in ('configs', 'workloads', 'layer_metrics'):
        shutil.copytree(os.path.join(BENCH_DIR, d), bench / d)
    man = load_json(os.path.join(ROOT, 'BENCHMARK.json'))
    (bench / 'configs' / 'toy_glm.json').write_text(json.dumps({
        'name': 'toy_glm', 'source': 'test', 'model': 'glm4_moe_lite_toy', 'reference': 'glm4_moe_lite', 'sizes': TOY_SIZES,
        'recipe': {'clip_grad': 1.0, 'weight_decay': 0.1, 'betas': [0.9, 0.95]},
        'train_args': ['-b', '8', '--opt', 'adamw', '--opt-betas', '0.9', '0.95', '--weight-decay', '0.1', '--clip-grad', '1.0',
                       '--grad-checkpointing', '--dataset', 'tokens', '--seq-len', '64'],
        'reduced': [], 'reference_block_q': 32, 'limits': {'lm_train': TOY_LIMITS}, 'limits_lm': {'route_agreement_min': 0.99}}))
    (bench / 'workloads' / 'toy_glm_train.json').write_text(json.dumps({
        'config': 'toy_glm', 'runner': 'lm_train', 'chips': 1,
        'traffic': {'warmup_steps': 3, 'token_stream': {'name': 'toy', 'data_seed': 1, 'tokens': 64 * 8 * 400,
                                                        'validation_tokens': 64 * 8}}}))
    man['configs'].append({'name': 'toy_glm', 'source': 'test', 'file': 'benchmarks/configs/toy_glm.json', 'reduced': [], 'why': 'test'})
    man['workloads'].append({'name': 'toy_glm_train', 'config': 'toy_glm', 'traffic': 'toy_tokens', 'chips': 1, 'why': 'test'})
    for metric in man['end_to_end'] + man['per_layer']:
        if CELL in metric.get('workloads', ()):
            metric['workloads'].append('toy_glm_train')
    (tmp / 'BENCHMARK.json').write_text(json.dumps(man))
    return Manifest(bench_dir=str(bench), manifest_path=str(tmp / 'BENCHMARK.json')), str(tmp / 'scratch')


def _run(toy, seconds, **kw):
    m, scratch = toy
    cell = m.cell('toy_glm_train')
    lines = []
    record = runner_module(cell['runner']).run(cell, m.config(cell['config']), seed=2 ** 31 + 11, seconds=seconds, trace=False,
                                               process_start=time.perf_counter(), scratch=scratch, log=lines.append, **kw)
    return record, lines


@pytest.fixture(scope='module')
def sound(toy):
    return _run(toy, 0.4, control_precision='bfloat16')


def test_the_new_runner_runs_a_cell_added_by_files_and_prints_the_contracts_line(toy, sound):
    from benchmarks import run as bench_run
    record, lines = sound
    assert record['correct'] and record['failed'] == 0 and record['attempted'] > 0 and record['compiles_in_window'] == 0
    assert record['runner'] == 'train' and record['batch_size'] == 8 and record['lm']['seq_len'] == 64
    compared = {l.split()[1].rstrip(':') for l in lines if l.startswith('check ')}
    assert {'loss_gap_step3', 'first_grad_norm_gap', 'param_change_norm_gap', 'feed_repeated_rows', 'feed_targets_off',
            'moe_dropped_slots', 'route_agreement', 'first_loss', 'compiles_in_window'} <= compared
    assert 'ema_change_norm_gap' not in compared and record['numbers']['route_agreement'] == 1.0
    # the same numbers as data, beside their limits: what the result line ends with
    assert set(record['checks']) == compared and all(c['ok'] for c in record['checks'].values())
    assert record['checks']['route_agreement'] == {'value': 1.0, 'limit': 0.99, 'how': 'at least', 'ok': True}
    steps = record['steps']
    assert all(len(record['counters'][k]) == steps for k in ('moe.local_slots', 'moe.load_max', 'moe.dropped_slots', 'lm.tokens'))
    assert set(record['counters']['lm.tokens']) == {8 * 64} and set(record['counters']['moe.dropped_slots']) == {0}
    assert record['lm']['tokens_per_s'] == pytest.approx(record['end_to_end']['train_img_per_s'] * 64)
    device = {'platform': 'cpu', 'kind': 'cpu', 'count': 1}
    plain = bench_run.result_line(toy[0], 'toy_glm_train', record, device, trace=False)
    assert set(plain['metrics']) == {'train_img_per_s', 'setup_s'} and plain['correct']
    # the traced line: the readers the image cells have read this run, the new ones read its counters and scopes
    scopes = {'scope_s': {'glm.mla.core': 0.20, 'glm.mla.proj': 0.05, 'glm.moe.experts': 0.04, 'glm.moe.route': 0.03,
                          'glm.moe.shared': 0.02, 'glm.head_loss': 0.05, 'glm.dense_ffn': 0.03, 'glm.embed': 0.001},
              'busy_s': 0.5, 'unscoped': [['fusion', 0.05]]}
    traced = dict(record, device_kind='TPU v5 lite', trace={
        'busy_s': 0.5, 'window_s': 1.0, 'idle_share': 0.5, 'work': 5, 'idle_total_s': 0.5, 'scopes': scopes,
        'breakdown': {'device_ops': [], 'idle_gaps': []}})
    line = json.loads(json.dumps(bench_run.result_line(toy[0], 'toy_glm_train', traced, device, trace=True)))
    got = {k: v['value'] for k, v in line['metrics'].items()}
    assert set(METRICS) | {'step_mfu.train'} <= set(got) and not (NOT_ITS | set(COUNTED)) & set(got)
    assert not [n for n in got if n.startswith('attn_')]                                   # the other family's
    assert {'step_device_ms.train', 'step_wall_ms.train', 'dispatch_host_ms.train', 'input_host_ms.train', 'step_call_ms.train',
            'device_idle_share.train', 'input_batch_wait_ms.train', 'loop_bookkeeping_ms.train', 'setup_compile_s'} <= set(got)
    assert got['mla_device_ms.train'] == pytest.approx(50.0) and got['moe_device_ms.train'] == pytest.approx(18.0)
    assert got['moe_route_device_ms.train'] == pytest.approx(6.0)
    assert record['needed_macs'] == lm_flops.forward_macs(TOY_SIZES, 64, 8, sum(record['counters']['moe.local_slots']) / steps)
    assert record['needed_step_flops'] == lm_flops.train_flops(record['needed_macs']) and record['lm']['expert_layers'] == 3
    slots = sum(record['counters']['moe.local_slots']) / steps
    macs = lm_flops.forward_macs(TOY_SIZES, 64, 8, slots)
    assert got['step_mfu.train'] == pytest.approx(100 * lm_flops.train_flops(macs) / 0.1 / 197e12)
    assert got['mla_core_mfu.train'] == pytest.approx(100 * 6 * macs['mla_core'] / 0.04 / 197e12)
    assert got['moe_experts_mfu.train'] == pytest.approx(100 * 6 * macs['moe_experts'] / 0.008 / 197e12)
    assert any(l.startswith('device scopes cover 84.2 %') for l in device_scopes.scope_table(traced))
    # the two readings that are no metric, as the free text a traced run prints
    said = {l.split()[1].rstrip(':'): float(l.split()[2]) for l in lm_readers.lines(traced)}
    assert list(said) == COUNTED and said['moe_load_max_over_mean.train'] >= 1.0
    assert said['moe_slots_per_expert.train'] == pytest.approx(slots / (2 * 3), rel=1e-5)
    assert all('nothing to read' in l for l in lm_readers.lines({}))
    # and both definitions of the memory peak, until one is chosen
    assert any(l.startswith('memory_peak_bytes: ') and 'peaks.memory_peak_bytes' in l for l in lines)
    assert record['memory_peak_bytes'] <= record['memory_peak_bytes_summed']


@pytest.mark.parametrize('name', NEW)
def test_a_new_reader_returns_none_where_there_is_nothing_to_read(name):
    """A parent without the scopes and counters, a run of another runner, an empty record: no value, no raise."""
    read = lm_readers.READERS[name].read
    assert read({}) is None
    assert read({'runner': 'train', 'steps': 3, 'sizes': {'embed_dim': 768}, 'device_kind': 'TPU v5 lite',
                 'trace': {'busy_s': 0.5, 'window_s': 1.0, 'idle_share': 0.5, 'work': 5}}) is None


def test_the_lower_precision_control_is_not_correct(toy, sound):
    record, _ = sound
    limits = toy[0].config('toy_glm')['limits']['lm_train']
    numbers = lambda d: {k: (v, '') for k, v in d.items() if k != 'route_agreement'}  # noqa: E731
    assert check.judge(numbers(record['numbers']), limits, out=lambda s: None)
    assert not check.judge(numbers(record['control_numbers']), limits, out=lambda s: None)
    assert record['control_numbers']['route_agreement'] < 1.0 and record['control_correct'] is False
    assert any(l.startswith('control bfloat16 check ') and l.split('(')[0].rstrip().endswith('OVER') for l in sound[1])


def test_a_step_that_returns_its_state_unchanged_is_not_correct(toy):
    import jax.numpy as jnp
    stuck = lambda task, batch, lr, step=0: {'loss': jnp.float32(7.2), 'grad_norm': jnp.float32(1.0)}  # noqa: E731
    record, lines = _run(toy, 0.2, inner_step=stuck)
    over = {l.split()[1].rstrip(':') for l in lines if l.startswith('check ') and l.split('(')[0].rstrip().endswith('OVER')}
    assert not record['correct'] and {'param_change_norm_gap', 'first_grad_norm_gap', 'moe_counters_missing'} <= over
    assert record['numbers']['param_change_norm_gap'] == pytest.approx(1.0)


def test_device_time_is_reduced_by_the_innermost_declared_scope():
    names = device_scopes.declared_scopes()
    assert names >= set(device_scopes.SCOPE_PARTS) and len(device_scopes.SCOPE_PARTS) == 9    # a later family may declare more
    of = lambda op: device_scopes.scope_of(op, names)  # noqa: E731
    assert of('jit(train_step)/transpose(jvp(glm.mtp))/checkpoint/glm.mla.core/checkpoint/bhqd,bhkd->bhqk/dot_general') == 'glm.mla.core'
    assert of('jit(train_step)/jvp(glm.moe.route)/sort') == 'glm.moe.route' and of('jit(train_step)/adamw/mul') is None
    hlo = '\n'.join([
        '  %fusion = bf16[2048,2048]{1,0} fusion(%p), kind=kOutput, calls=%fc, metadata={op_name="jit(f)/jvp(glm.mla.core)/dot_general" source_file="x.py"}',
        '  ROOT %copy-done = bf16[2,2]{1,0} copy-done(%copy-start), metadata={op_name="jit(f)/glm.embed/gather"}',
        '  %ragged-dot-none.3 = bf16[8,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}',
        '  %add.1 = f32[] add(%a, %b), metadata={op_name="jit(f)/adamw/add"}',
        # a Pallas call's text runs over three lines, and names another "metadata" first
        '  %splash_mha_fwd.6 = (f32[2,8]{1,0}) custom-call(%q), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={',
        '"xprof_metadata":"{\\"block_q\\": 1024}"',
        '}}, metadata={op_name="jit(f)/jvp(glm.mla.core)/vmap(jit(_splash_attention))/pallas_call" stack_frame_id=2}, backend_config={}',
        '  %mul.2 = f32[] multiply(%a, %b)'])
    assert device_scopes.instruction_scopes(hlo, names) == {'fusion': 'glm.mla.core', 'copy-done': 'glm.embed',
                                                            'ragged-dot-none.3': 'glm.moe.experts',
                                                            'splash_mha_fwd.6': 'glm.mla.core'}
    # on the recorded chip trace: its fusions under one scope, its copies under another; together they are the busy time
    from benchmarks.harness import trace
    path = os.path.join(BENCH_DIR, 'fixtures', 'toy_matmuls.xplane.pb')
    seen = {device_scopes.instruction_of(n) for ops in trace.read_planes(path)[0].values() for n, _, _ in ops}
    assert any(n.startswith('fusion') for n in seen) and any(n.startswith('copy') for n in seen)
    hlo = '\n'.join(f'  %{n} = bf16[2,2]{{1,0}} op(%p), metadata={{op_name="jit(f)/{"jvp(glm.mla.core)" if n.startswith("fusion") else "glm.embed"}/x"}}'
                    for n in sorted(seen))
    got = device_scopes.reduce_scopes(path, hlo, names)
    want = load_json(os.path.join(BENCH_DIR, 'fixtures', 'toy_matmuls.expected.json'))
    assert got['busy_s'] == pytest.approx(want['busy_s'], rel=1e-9) and got['unscoped'] == []
    assert got['scope_s']['glm.mla.core'] > 100 * got['scope_s']['glm.embed'] > 0
    assert got['scope_s']['glm.mla.core'] + got['scope_s']['glm.embed'] == pytest.approx(got['busy_s'], rel=1e-6)
    assert device_scopes.reduce_scopes(path, '', names)['scope_s'] == {} and device_scopes.reduce_scopes(path, hlo, set())['busy_s'] == 0.0
    # a real step program's compiled text names the scopes (the CPU's here; the chip's in a traced run)
    import jax
    import timm_tpu
    from flax import nnx
    model = timm_tpu.create_model('glm4_moe_lite_toy', seed=0)
    graphdef, state = nnx.split(model)
    ids = jax.numpy.zeros((1, 64), 'int32')
    text = jax.jit(lambda st: nnx.merge(graphdef, st)(ids)).lower(state).compile().as_text()
    assert {'glm.embed', 'glm.mla.proj', 'glm.mla.core', 'glm.dense_ffn', 'glm.moe.route', 'glm.moe.shared'} <= set(
        device_scopes.instruction_scopes(text, names).values())
