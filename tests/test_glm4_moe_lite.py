"""GLM-4.7-Flash (`glm4_moe_lite`) at a toy size on the CPU, against the plain
reference (`benchmarks/reference/glm4_moe_lite.py`): the model, the latent
attention layer, the expert layer and its share, the causal-LM task and the
token feed. Seeded random weights, float32 on both sides: they differ by
summation order (1e-6 was seen), so 1e-4 is a decade from a real difference.
"""
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import nnx

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import timm_tpu  # noqa: E402
from benchmarks.harness import check, program, weights  # noqa: E402
from benchmarks.reference import glm4_moe_lite as ref  # noqa: E402
from benchmarks.reference import lm_train_step  # noqa: E402
from timm_tpu.layers import LatentAttention, SparseMoe, build_rotary_pos_embed_1d  # noqa: E402
from timm_tpu.optim import create_optimizer_v2  # noqa: E402
from timm_tpu.task import CausalLMTask  # noqa: E402

TOL = 1e-4
SIZES = dict(vocab_held=256, hidden_size=64, num_hidden_layers=3, num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
             qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16, intermediate_size=160, moe_intermediate_size=32,
             n_routed_experts=8, num_experts_per_tok=2, experts_held=2, expert_offset=0, n_shared_experts=1,
             routed_scaling_factor=1.8, first_k_dense_replace=1, num_nextn_predict_layers=1, rope_theta=1e6,
             rms_norm_eps=1e-5, mtp_loss_weight=0.3)
S = 64


def _batch(seed=0, rows=2):
    ids = np.random.default_rng(seed).integers(0, 256, (rows, S + 1))
    target = np.concatenate([ids[:, 1:S], np.full((rows, 1), -1)], axis=1)
    return jnp.asarray(ids[:, :S], jnp.int32), jnp.asarray(target, jnp.int32)


@pytest.fixture(scope='module')
def toy():
    """The toy model with the benchmark's seeded weights and a non-zero router bias, and the same for the reference."""
    params = weights.make(11, ref.init_spec(SIZES))
    model = timm_tpu.create_model('glm4_moe_lite_toy', seed=0)
    program.load_weights(model, params)
    biases = {}
    for i, (prefix, block) in enumerate([(f'blocks.{j}.', b) for j, b in enumerate(model.blocks)] + [('mtp.block.', model.mtp.block)]):
        if not block.dense:
            biases[prefix] = jax.random.normal(jax.random.key(100 + i), (8,)) * 0.3
            block.mlp.score_bias[...] = biases[prefix]
    return model, params, biases


def test_the_entry_points_hold_what_the_configuration_says():
    share = nnx.eval_shape(lambda: timm_tpu.create_model('glm4_moe_lite_flash_ep8'))
    leaves = program.named_leaves(nnx.state(share, nnx.Param))
    assert sum(math.prod(v.shape) for v in leaves.values()) == 706_518_528           # ISSUE 26's table: 706.5M
    assert leaves['blocks.1.mlp.router'].shape == (2048, 64) and leaves['blocks.1.mlp.w_gate'].shape == (8, 2048, 1536)
    assert leaves['embed.embedding'].shape == (19360, 2048) and leaves['blocks.0.mlp.fc1_g.kernel'].shape == (2048, 10240)
    assert share.task_kind == 'causal_lm' and len(share.blocks) == 5 and share.mtp is not None
    assert set(share.group_matcher()) == {'stem', 'blocks'}
    full = nnx.eval_shape(lambda: timm_tpu.create_model('glm4_moe_lite_flash'))
    assert len(full.blocks) == 47 and full.vocab_held == 154880 and full.blocks[1].mlp.experts_held == 64


def test_model_matches_the_reference_logits_of_both_heads_loss_and_every_gradient_leaf(toy):
    model, params, biases = toy
    ids, target = _batch()
    task = CausalLMTask(model, loss_chunk=32)
    ref_forward = jax.jit(lambda p, i, t, b: ref.forward(SIZES, p, i, t, block_q=32, biases=b))
    out = [ref_forward(params, ids[b], target[b], biases) for b in range(2)]
    nxt = jnp.where(target < 0, 0, target)

    @nnx.jit
    def heads(model):
        h = model.forward_features(ids)
        return model.forward_head(h), model.forward_mtp(h, nxt), model.routes(ids, nxt)
    logits, logits_mtp, routes = heads(model)
    assert float(jnp.abs(logits - jnp.stack([o['logits'] for o in out])).max()) < TOL
    assert float(jnp.abs(logits_mtp - jnp.stack([o['logits_mtp'] for o in out])).max()) < TOL
    assert bool((routes.transpose(1, 0, 2, 3) == jnp.stack([o['routes'] for o in out])).all())
    # with grad checkpointing on, as the cell trains
    model.set_grad_checkpointing(True)
    graphdef, state, rest = nnx.split(model, nnx.Param, ...)
    loss_fn = lambda st: task.loss_forward(nnx.merge(graphdef, st, rest, copy=True), {'input': ids, 'target': target})[0]  # noqa: E731
    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(state)
    model.set_grad_checkpointing(False)
    n_main, n_mtp = 2 * (S - 1), 2 * (S - 2)
    ref_fn = lambda p: sum(ref.loss(SIZES, p, ids[b], target[b], n_main, n_mtp, block_q=32, biases=biases)[0] for b in range(2))  # noqa: E731
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(ref_fn))(params)
    assert abs(float(loss) - float(ref_loss)) < TOL and abs(float(ref_loss) - 1.3 * math.log(256)) < 0.5
    got = program.named_leaves(grads)
    assert set(got) == set(ref_grads)
    gap, leaf = check.worst_leaf_gap({k: float(jnp.linalg.norm(v)) for k, v in got.items()},
                                     {k: float(jnp.linalg.norm(v)) for k, v in ref_grads.items()})
    assert gap < TOL, (gap, leaf)
    assert max(float(jnp.abs(got[k] - ref_grads[k]).max()) for k in got) < TOL
    # the bias steers the choice and takes no gradient: without it other experts are chosen
    plain = ref_forward(params, ids[0], target[0], None)
    assert not bool((plain['routes'] == out[0]['routes']).all())


def test_latent_attention_prefill_is_an_uncompressed_multi_head_attention_from_the_same_w_kvb():
    dim, H, nope, rd, vd, rank = 64, 4, 12, 8, 16, 16
    attn = LatentAttention(dim, H, 24, rank, nope, rd, vd, block_q=16, rngs=nnx.Rngs(3))
    x = jax.random.normal(jax.random.key(0), (2, S, dim))
    rope = build_rotary_pos_embed_1d(S, rd, 1e6)
    got = nnx.jit(lambda a, x: a(x, rope))(attn, x)
    # the same layer written out: per-head key and value matrices split from W_kvb, one S x S causal softmax
    c_q = attn.q_norm(x @ attn.q_a.kernel[...])
    q = (c_q @ attn.q_b.kernel[...]).reshape(2, S, H, nope + rd)
    kv_a = x @ attn.kv_a.kernel[...]
    c_kv, k_rope = attn.kv_norm(kv_a[..., :rank]), kv_a[..., rank:]
    w_kvb = attn.kv_b.kernel[...].reshape(rank, H, nope + vd)
    k_nope, v = jnp.einsum('bsr,rhd->bshd', c_kv, w_kvb[..., :nope]), jnp.einsum('bsr,rhd->bshd', c_kv, w_kvb[..., nope:])
    turn = lambda t: ref.rope(jnp.moveaxis(t, 1, -2), 1e6)  # noqa: E731  (.., S, D)
    q = jnp.concatenate([jnp.moveaxis(q[..., :nope], 1, 2), turn(q[..., nope:])], axis=-1)          # (B, H, S, D)
    k = jnp.concatenate([jnp.moveaxis(k_nope, 1, 2), jnp.broadcast_to(turn(k_rope[:, :, None])[:, :, :, :], (2, 1, S, rd)).repeat(H, 1)], -1)
    scores = jnp.einsum('bhqd,bhkd->bhqk', q, k) / math.sqrt(nope + rd)
    scores = jnp.where(jnp.tril(jnp.ones((S, S), bool)), scores, -jnp.inf)
    out = jnp.einsum('bhqk,bkhd->bqhd', jax.nn.softmax(scores, -1), v).reshape(2, S, H * vd) @ attn.o.kernel[...]
    assert float(jnp.abs(got - out).max()) < 1e-5
    # causal: a later token changes no earlier output; and the query blocks change nothing
    x2 = x.at[:, 40:].set(0.0)
    assert float(jnp.abs(attn(x2, rope)[:, :40] - got[:, :40]).max()) < 1e-6
    attn.block_q = S
    assert float(jnp.abs(attn(x, rope) - got).max()) < 1e-6


def test_the_shares_routed_parts_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The share test: four shares of 2 experts each against the reference given all 8."""
    cfg = dict(SIZES, experts_held=8)
    spec = {k[len('blocks.1.'):]: v for k, v in ref.init_spec(cfg).items() if k.startswith('blocks.1.mlp.')}
    p = weights.make(5, spec)
    x = jax.random.normal(jax.random.key(1), (2 * S, 64))
    bias = jax.random.normal(jax.random.key(2), (8,)) * 0.3
    whole, _ = ref.experts(cfg, {'blocks.1.' + k: v for k, v in p.items()}, 'blocks.1.', x, 'float32', bias)
    total, slots = 0.0, 0
    for rank in range(4):
        layer = SparseMoe(64, 32, 8, 2, experts_held=2, expert_offset=2 * rank, n_shared=1, routed_scaling_factor=1.8,
                          rngs=nnx.Rngs(0))
        layer.router[...], layer.score_bias[...] = p['mlp.router'], bias
        for name in ('w_gate', 'w_up', 'w_down'):
            getattr(layer, name)[...] = p['mlp.' + name][2 * rank:2 * rank + 2]
        program.load_weights(layer.shared, {k[len('mlp.shared.'):]: v for k, v in p.items() if k.startswith('mlp.shared.')})
        part, counters = layer.routed(x)
        total, slots = total + part, slots + int(counters['moe.local_slots'])
        assert int(counters['moe.dropped_slots']) == 0
    assert slots == 2 * S * 2                                         # every (token, choice) slot lives on exactly one share
    assert float(jnp.abs(total + layer.shared(x) - whole).max()) < TOL
    # the reference given one share gives that share's part plus the shared expert
    one, _ = ref.experts(dict(SIZES, expert_offset=6), {'blocks.1.' + k: (v[6:] if k.startswith('mlp.w_') else v) for k, v in p.items()},
                         'blocks.1.', x, 'float32', bias)
    assert float(jnp.abs(part + layer.shared(x) - one).max()) < TOL


def test_no_token_is_dropped_when_every_token_picks_the_same_experts():
    layer = SparseMoe(64, 32, 8, 2, experts_held=2, expert_offset=0, n_shared=0, routed_scaling_factor=1.8, rngs=nnx.Rngs(1))
    layer.score_bias[...] = jnp.asarray([9.0, 8.0, 0, 0, 0, 0, 0, 0])          # the bias alone decides: experts 0 and 1
    x = jax.random.normal(jax.random.key(3), (2, S, 64))
    y, counters = jax.jit(lambda m, x: m(x))(layer, x)
    T = 2 * S
    assert int(counters['moe.local_slots']) == 2 * T and int(counters['moe.dropped_slots']) == 0
    assert int(counters['moe.load_max']) == T                          # the worst case: the buffer is full
    scores = jax.nn.sigmoid(x.reshape(T, 64) @ layer.router[...])[:, :2]
    w = scores / scores.sum(-1, keepdims=True) * 1.8
    dense = sum(w[:, e:e + 1] * ((jax.nn.silu(x.reshape(T, 64) @ layer.w_gate[...][e]) * (x.reshape(T, 64) @ layer.w_up[...][e]))
                                 @ layer.w_down[...][e]) for e in range(2))
    assert float(jnp.abs(y.reshape(T, 64) - dense).max()) < TOL
    # and nothing held here is chosen: the layer's part is zero, no slot counted
    layer.score_bias[...] = jnp.asarray([0, 0, 9.0, 8.0, 0, 0, 0, 0])
    y, counters = layer(x)
    assert int(counters['moe.local_slots']) == 0 and float(jnp.abs(y).max()) == 0.0


def test_causal_lm_task_two_steps_follow_the_reference(toy):
    _, params, _ = toy
    model = timm_tpu.create_model('glm4_moe_lite_toy', seed=0)
    model.set_grad_checkpointing(True)
    program.load_weights(model, params)
    opt = create_optimizer_v2(model, opt='adamw', lr=1e-3, weight_decay=0.1, betas=(0.9, 0.95))
    task = CausalLMTask(model, optimizer=opt, clip_grad=1.0, loss_chunk=32)
    steps = [dict(zip(('input', 'target'), _batch(seed)), lr=1e-3) for seed in (1, 2)]
    losses, first = [], None
    for i, step in enumerate(steps):
        metrics = task.train_step({'input': step['input'], 'target': step['target']}, lr=step['lr'], step=i)
        losses.append(float(metrics['loss']))
        first = first or program.first_grad_norms(task)
        assert int(metrics['moe.dropped_slots']) == 0 and int(metrics['lm.tokens']) == 2 * S
        assert 0 < int(metrics['moe.load_max']) <= int(metrics['moe.local_slots']) <= 3 * 2 * S * 2
    got = {'losses': losses, 'first_grad_norms': first,
           'param_change_norms': {k: float(jnp.linalg.norm(v - params[k]))
                                  for k, v in program.named_leaves(nnx.state(model, nnx.Param)).items()}}
    want = lm_train_step.follow(ref, SIZES, lambda: weights.make(11, ref.init_spec(SIZES)), steps, clip=1.0,
                                weight_decay=0.1, betas=(0.9, 0.95), block_q=32)
    numbers = check.training_numbers(got, want)
    assert all(v[0] < 1e-3 for v in numbers.values()), numbers      # Adam's division turns 1e-7 of gradient into 1e-4 of step
    assert want['routes'].shape == (2, 3, S, 2)


def test_the_token_loader_is_seeded_repeats_no_sequence_and_reads_through_train_main(tmp_path):
    import train
    from timm_tpu.data import create_dataset
    from timm_tpu.data.loader import ThreadedLoader
    from timm_tpu.utils import tracing
    rng = np.random.default_rng(0)
    rng.integers(0, 256, S * 24 + 7, dtype=np.int32).tofile(tmp_path / 'train.bin')
    rng.integers(0, 256, S * 8, dtype=np.int32).tofile(tmp_path / 'validation.bin')
    data = create_dataset('tokens', root=str(tmp_path), split='train', is_training=True, seq_len=S, num_classes=256)
    assert len(data) == 24 and data[3][0].dtype == np.int32
    ids, target = data[3]
    assert (target[:-1] == ids[1:]).all() and target[-1] == -1 and (ids == np.fromfile(tmp_path / 'train.bin', np.int32)[3 * S:4 * S]).all()

    def epoch(seed):
        return [x for x, _ in ThreadedLoader(data, batch_size=4, is_training=True, num_workers=2, seed=seed)]
    a, b, c = epoch(1), epoch(1), epoch(2)
    rows = [row.tobytes() for x in a for row in x]
    assert len(a) == 6 and a[0].shape == (4, S) and len(set(rows)) == 24                       # every window once
    assert sorted(rows) == sorted(r.tobytes() for x in b for r in x) == sorted(r.tobytes() for x in c for r in x)
    assert [x.tobytes() for x in a] != [x.tobytes() for x in c]                               # the seed drives the order
    with pytest.raises(ValueError, match='outside'):
        create_dataset('tokens', root=str(tmp_path), split='train', seq_len=S, num_classes=16)[0]
    mark = tracing.now_ns()
    out = train.main(['--model', 'glm4_moe_lite_toy', '--dataset', 'tokens', '--data-dir', str(tmp_path), '--seq-len', str(S),
                      '-b', '8', '--epochs', '1', '--opt', 'adamw', '--opt-betas', '0.9', '0.95', '--weight-decay', '0.1',
                      '--clip-grad', '1.0', '--grad-checkpointing', '--output', str(tmp_path / 'out'), '--experiment', 't',
                      '-j', '2', '--seed', '7'])
    assert abs(out['loss'] - math.log(256)) < 0.5 and 0.0 <= out['top1'] <= out['top5'] <= 100.0
    spans = [s for s in tracing.snapshot()['spans'] if s.start_ns >= mark]
    assert sum(s.name == 'task.train_step' for s in spans) == 3 and any(s.name == 'loader.batch_wait' for s in spans)
    with pytest.raises(ValueError, match='go together'):
        train.main(['--model', 'test_vit', '--dataset', 'tokens', '--data-dir', str(tmp_path)])


def test_latent_attention_takes_the_pallas_kernel_where_its_shapes_apply_and_agrees_with_the_xla_path():
    """Head widths of 128 and 256 positions: `causal_flash_supported`, so the layer's core is the registered
    kernel (interpreted here). Two separate jits, as `train.main` traces the layer in more than one program."""
    from timm_tpu.kernels import causal_flash_supported
    attn = LatentAttention(64, 2, 24, 16, 64, 64, 128, block_q=128, rngs=nnx.Rngs(5))
    x = jax.random.normal(jax.random.key(0), (1, 256, 64))
    rope = build_rotary_pos_embed_1d(256, 64, 1e6)
    q, k, v = attn.qkv(x, rope)
    assert causal_flash_supported(q, k, v) and not causal_flash_supported(q[..., :20], k[..., :20], v[..., :20])
    assert not causal_flash_supported(q[:, :, :64], k[:, :, :64], v[:, :, :64])
    loss = lambda a, x: (a(x, rope) ** 2).sum()  # noqa: E731
    out = nnx.jit(lambda a, x: a(x, rope))(attn, x)
    value, grads = nnx.jit(nnx.value_and_grad(loss))(attn, x)
    try:
        import timm_tpu.kernels as kernels
        saved = kernels.causal_flash_supported
        kernels.causal_flash_supported = lambda q, k, v: False               # the same layer on the XLA path
        want = nnx.jit(lambda a, x: a(x, rope))(attn, x)
        want_value, want_grads = nnx.jit(nnx.value_and_grad(loss))(attn, x)
    finally:
        kernels.causal_flash_supported = saved
    assert float(jnp.abs(out - want).max()) < TOL and abs(float(value) - float(want_value)) < TOL * float(want_value)
    gaps = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), nnx.state(grads), nnx.state(want_grads))
    assert max(jax.tree.leaves(gaps)) < 1e-3, gaps


def test_a_long_sequence_that_falls_back_to_the_xla_core_on_a_tpu_says_so_once(monkeypatch, caplog):
    """Head widths the kernel does not take (96 and 128) at 2048 positions: the layer still runs, on the XLA
    query-block core, and logs the 10x once a shape; short sequences and the CPU stay quiet."""
    from timm_tpu.layers import latent_attention
    attn = nnx.eval_shape(lambda: LatentAttention(64, 2, 24, 16, 64, 32, 128, rngs=nnx.Rngs(0)))
    graphdef, state = nnx.split(attn)
    trace = lambda s: jax.eval_shape(lambda st, x: nnx.merge(graphdef, st)(x, build_rotary_pos_embed_1d(s, 32, 1e6)),  # noqa: E731
                                     state, jax.ShapeDtypeStruct((1, s, 64), jnp.float32))
    caplog.set_level('WARNING', logger=latent_attention.__name__)
    assert trace(2048).shape == (1, 2048, 64) and not caplog.records          # the CPU: the tests' own path
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    monkeypatch.setattr(latent_attention, '_WARNED_SHAPES', set())
    trace(1024)
    assert not caplog.records
    trace(2048), trace(2048)
    assert len(caplog.records) == 1 and 'causal_flash_supported' in caplog.records[0].getMessage()


def test_every_parameter_of_the_family_has_one_partition_rule_and_an_expert_stack_is_sharded():
    """What the zoo's partition sweep holds every family to (`analysis/source_rules.py`), here for the toy and
    the share: no leaf falls to the catch-all, so the expert stacks, the largest leaves, are not replicated."""
    import timm_tpu
    from timm_tpu.parallel import create_mesh, default_partition_rules, match_rule
    from timm_tpu.parallel.sharding import spec_for_param
    from timm_tpu.utils.serialization import flatten_pytree
    rules = default_partition_rules()
    for name in ('glm4_moe_lite_toy', 'glm4_moe_lite_flash_ep8'):
        model = nnx.eval_shape(lambda n=name: timm_tpu.create_model(n))
        for path in flatten_pytree(nnx.state(model, nnx.Param)):
            assert sum(r.matches(path) for r in rules[:-1]) == 1, path
    assert match_rule('blocks.1.mlp.w_down', rules)[1].name == 'expert-stack' and match_rule('mtp.block.mlp.router', rules)[1].name == 'router'
    mesh = create_mesh(devices=jax.devices()[:8], fsdp=8)
    assert tuple(spec_for_param('blocks.1.mlp.w_gate', (8, 2048, 1536), mesh)) == (None, None, 'fsdp')
    assert tuple(spec_for_param('blocks.1.mlp.w_down', (8, 1536, 2048), mesh)) == (None, None, 'fsdp')
    assert tuple(spec_for_param('blocks.1.mlp.router', (2048, 64), mesh)) == ()


def _written_out(attn, x):
    """The layer as an uncompressed multi-head attention from the same kernels, as
    `test_latent_attention_prefill_is_an_uncompressed_multi_head_attention_from_the_same_w_kvb` writes it: per-head
    key and value matrices split from W_kvb, one S x S causal softmax, float32."""
    B, S_, _ = x.shape
    H, nope, rd, vd, rank = attn.num_heads, attn.nope, attn.rope, attn.v_dim, attn.kv_lora_rank
    q = (attn.q_norm(x @ attn.q_a.kernel[...]) @ attn.q_b.kernel[...]).reshape(B, S_, H, nope + rd)
    kv_a = x @ attn.kv_a.kernel[...]
    c_kv, k_rope = attn.kv_norm(kv_a[..., :rank]), kv_a[..., rank:]
    w_kvb = attn.kv_b.kernel[...].reshape(rank, H, nope + vd)
    k_nope, v = jnp.einsum('bsr,rhd->bshd', c_kv, w_kvb[..., :nope]), jnp.einsum('bsr,rhd->bshd', c_kv, w_kvb[..., nope:])
    turn = lambda t: ref.rope(jnp.moveaxis(t, 1, -2), 1e6)  # noqa: E731  (.., S, D)
    q = jnp.concatenate([jnp.moveaxis(q[..., :nope], 1, 2), turn(q[..., nope:])], axis=-1)          # (B, H, S, D)
    k = jnp.concatenate([jnp.moveaxis(k_nope, 1, 2), jnp.broadcast_to(turn(k_rope[:, :, None]), (B, 1, S_, rd)).repeat(H, 1)], -1)
    scores = jnp.einsum('bhqd,bhkd->bhqk', q, k) / math.sqrt(nope + rd)
    scores = jnp.where(jnp.tril(jnp.ones((S_, S_), bool)), scores, -jnp.inf)
    return jnp.einsum('bhqk,bkhd->bqhd', jax.nn.softmax(scores, -1), v).reshape(B, S_, H * vd) @ attn.o.kernel[...]


@pytest.mark.parametrize('sizes,rows,seq,dtype,kernel,tol', [
    ((64, 4, 24, 16, 12, 8, 16), 2, S, None, False, 1e-5),                # the toy size on the XLA query-block core
    ((64, 2, 24, 16, 64, 64, 128), 1, 256, jnp.bfloat16, True, 2e-2),     # a kernel-eligible size, the kernel interpreted
], ids=['float32-xla-core', 'bfloat16-interpreted-kernel'])
def test_latent_attention_output_and_every_parameter_gradient_are_the_written_out_attentions(sizes, rows, seq, dtype, kernel, tol):
    """The head-major products (column blocks of `q_b`, `kv_b` and the (H, D, dim) view of `o`) against the written-out
    attention, which multiplies the whole kernels and transposes: the output and the gradient of every parameter,
    each within `tol` of the reference's largest entry. float32 on float32 differs by summation order (1e-7 was
    seen); the bfloat16 layer read 4e-3 to 8e-3 against the float32 reference (1e-4 / 1e-3 absolute, the float32
    kernel test's limits, are below bfloat16's own rounding here), and a wrong column block or head order reads 1."""
    from timm_tpu.kernels import causal_flash_supported
    attn = LatentAttention(*sizes, block_q=16, dtype=dtype, rngs=nnx.Rngs(3))
    plain = LatentAttention(*sizes, rngs=nnx.Rngs(3))                        # the same parameters, float32 throughout
    x = jax.random.normal(jax.random.key(0), (rows, seq, sizes[0]))
    rope = build_rotary_pos_embed_1d(seq, sizes[5], 1e6)
    assert causal_flash_supported(*attn.qkv(x, rope)) == kernel
    layer = lambda a, x: a(x, rope).astype(jnp.float32)  # noqa: E731
    got, want = nnx.jit(layer)(attn, x), nnx.jit(_written_out)(plain, x)
    assert float(jnp.abs(got - want).max()) < tol * float(jnp.abs(want).max())
    grads = nnx.jit(nnx.grad(lambda a, x: (layer(a, x) ** 2).sum()))(attn, x)
    want_grads = nnx.jit(nnx.grad(lambda a, x: (_written_out(a, x) ** 2).sum()))(plain, x)
    gaps = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max() / jnp.abs(b).max()), nnx.state(grads), nnx.state(want_grads))
    assert len(jax.tree.leaves(gaps)) == 7 and max(jax.tree.leaves(gaps)) < tol, gaps


def test_the_cells_block_compiled_for_a_v5e_moves_no_activation_between_mla_products_and_the_core(v5e_chip, monkeypatch):
    """One block of the GLM cell (RMSNorm + `LatentAttention(2048, 20, 768, 512, 192, 64, 256)`, bfloat16, 2 x 8192,
    rematerialised with `CORE_OUT` saved), loss and gradient, compiled for the described chip: under `glm.mla.proj`
    the entry computation holds no `copy`, `slice` or `transpose` of 100 MB or more. The reshape-and-transpose
    spelling had thirteen (nine head transposes of 168 / 294 MB and four slices of a transposed kv, PERF.md section
    6, PR 44): q, k_nope and v leave their products as (B, H, S, D) and the core's output enters `o`'s as it is."""
    import re
    from timm_tpu.layers import RmsNorm
    from timm_tpu.layers.latent_attention import CORE_OUT
    from timm_tpu.parallel import mesh as mesh_mod
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')       # the kernel path; the test, not the program, says so
    monkeypatch.setattr(mesh_mod, '_GLOBAL_MESH', None)

    class Block(nnx.Module):
        def __init__(self, rngs):
            self.norm1 = RmsNorm(2048, eps=1e-5, dtype=jnp.bfloat16, rngs=rngs)
            self.attn = LatentAttention(2048, 20, 768, 512, 192, 64, 256, dtype=jnp.bfloat16, rngs=rngs)

        def __call__(self, x, rope):
            return x + self.attn(self.norm1(x), rope)

    graphdef, state = nnx.split(nnx.eval_shape(lambda: Block(nnx.Rngs(0))))

    def loss(state, x):
        policy = jax.checkpoint_policies.save_only_these_names(CORE_OUT)
        block = nnx.remat(lambda b, x, rope: b(x, rope), policy=policy)
        return (block(nnx.merge(graphdef, state), x, build_rotary_pos_embed_1d(8192, 64, 1e6)).astype(jnp.float32) ** 2).mean()
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e_chip), state)
    x = jax.ShapeDtypeStruct((2, 8192, 2048), jnp.bfloat16, sharding=v5e_chip)
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(shapes, x).compile().as_text()
    assert text.count('tpu_custom_call') >= 3                         # the kernel's forward, dq and dkv: the cell's path
    entry = text[text.index('\nENTRY '):]
    width = {'bf16': 2, 'f32': 4}
    moved = [(name, op, f'{dtype}[{dims}]') for name, dtype, dims, op in
             re.findall(r'^\s*(?:ROOT )?%?([\w.\-]+) = (bf16|f32)\[([\d,]+)\]\S* (copy|slice|transpose)\([^\n]*op_name="[^"]*glm\.mla\.proj', entry, re.M)
             if width[dtype] * math.prod(int(d) for d in dims.split(',')) >= 100e6]
    assert not moved, moved
    assert 'glm.mla.proj' in entry and re.search(r'= bf16\[2,20,8192,256\]\S* fusion\(', entry)    # the scope and a head-major result are there to be read
