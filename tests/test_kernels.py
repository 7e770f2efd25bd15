"""Pallas kernel tests (interpret mode on CPU; native on TPU).

1. The short-sequence attention pair (kernels/flash_attention.py): the four
   hand-written checks of the kernel it replaced on its (B, H, N, D) entry, the
   pair on the qkv product's layout (forward, dq, dk, dv) against `_sdpa`, the
   predicate a refusal a case, and `Attention` on the pair against itself on
   the plain path.
2. Registry behaviour (the every-module-registered-or-waived lint moved to
   timm_tpu/analysis, rule `kernel-registered`).
3. Auto-generated parity: one test per (kernel, declared regime case) pair,
   jitted kernel vs jitted XLA reference at the case's dry shapes.
4. Nothing a user sets selects a kernel: no constructor argument, no command
   line option, no environment read where a kernel is chosen.
"""
import ast
import dataclasses
import inspect
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import nnx

from timm_tpu.kernels import harness, registry
from timm_tpu.kernels.flash_attention import (
    flash_attention, flash_attention_supported, packed_attention, packed_attention_supported)
from timm_tpu.layers.attention import _sdpa

pytestmark = pytest.mark.kernels


def _rand(shape, seed=0, dtype=jnp.float32):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


# The four checks of the kernel this pair replaced, on the (B, H, N, D) entry of the pair (heads that fill a
# 128-lane column: 4 x 32), against `_sdpa`.

def test_flash_matches_sdpa():
    B, H, N, D = 2, 4, 256, 32
    q, k, v = _rand((B, H, N, D), 0), _rand((B, H, N, D), 1), _rand((B, H, N, D), 2)
    ref = _sdpa(q, k, v)
    out = flash_attention(q, k, v)
    assert float(jnp.abs(ref - out).max()) < 2e-2


def test_flash_key_mask():
    B, H, N, D = 2, 4, 256, 32
    q, k, v = _rand((B, H, N, D), 0), _rand((B, H, N, D), 1), _rand((B, H, N, D), 2)
    mask = jnp.asarray(np.random.RandomState(3).rand(B, N) > 0.3)
    ref = _sdpa(q, k, v, attn_mask=mask[:, None, None, :])
    out = flash_attention(q, k, v, mask=mask)
    assert float(jnp.abs(ref - out).max()) < 2e-2


def test_flash_unaligned_seq():
    # N=197: rows padded to 208 and key lanes to 256 inside the kernel, by a block larger than the array
    B, H, N, D = 1, 4, 197, 32
    q, k, v = _rand((B, H, N, D), 0), _rand((B, H, N, D), 1), _rand((B, H, N, D), 2)
    ref = _sdpa(q, k, v)
    out = flash_attention(q, k, v)
    assert out.shape == ref.shape
    assert float(jnp.abs(ref - out).max()) < 2e-2


def test_flash_grads_match():
    B, H, N, D = 1, 4, 128, 32
    q, k, v = _rand((B, H, N, D), 0), _rand((B, H, N, D), 1), _rand((B, H, N, D), 2)
    g1 = jax.grad(lambda q, k, v: (flash_attention(q, k, v) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: (_sdpa(q, k, v) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert float(jnp.abs(a - b).max()) < 5e-2


# The pair on its own layout: the qkv product's output in, the output product's input out.

def _unpacked(qkv, heads):
    B, N, c3 = qkv.shape
    return qkv.reshape(B, N, 3, heads, c3 // 3 // heads).transpose(2, 0, 3, 1, 4)      # (3, B, H, N, D)


@pytest.mark.parametrize('masked', [False, True], ids=['unmasked', 'key_padding'])
@pytest.mark.parametrize('seq', [37, 197])
@pytest.mark.parametrize('dtype,tol', [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)], ids=['float32', 'bfloat16'])
def test_packed_pair_matches_sdpa_forward_and_all_three_gradients(dtype, tol, seq, masked):
    """An unaligned length at a head width of 64 (two heads a 128-lane column): output and dq, dk, dv against
    `_sdpa` behind today's head transposes."""
    B, H, D = 3, 4, 64
    qkv = _rand((B, seq, 3 * H * D), 0, dtype) * 0.5
    weight = _rand((B, seq, H * D), 1)
    mask = jnp.asarray(np.random.RandomState(2).rand(B, seq) > 0.3) if masked else None

    def plain(qkv):
        q, k, v = _unpacked(qkv, H)
        out = _sdpa(q, k, v, attn_mask=None if mask is None else mask[:, None, None, :])
        return out.transpose(0, 2, 1, 3).reshape(B, seq, H * D)

    def loss(fn):
        return lambda qkv: (fn(qkv).astype(jnp.float32) * weight).sum()

    out, ref = packed_attention(qkv, H, mask), plain(qkv)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    assert float(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)).max()) < tol
    got, want = jax.grad(loss(lambda x: packed_attention(x, H, mask)))(qkv), jax.grad(loss(plain))(qkv)
    assert got.dtype == qkv.dtype and bool(jnp.isfinite(got.astype(jnp.float32)).all())
    for name, a, b in zip('qkv', _unpacked(got, H), _unpacked(want, H)):
        assert float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max()) < tol, name


_TAKEN = dict(batch=2, seq=197, heads=12, head_dim=64, mask=None, dropout_p=0.0, softmax_dtype=None,
              policy=None, fused=(True, True), mesh=None, exportable=False, itemsize=2)


@pytest.mark.parametrize('change,taken', [
    ({}, True),
    ({'seq': 1024}, True),
    ({'seq': 1025}, False),                                            # above the plain path's own bound
    ({'mask': ('bool', (2, 197))}, True),                              # key padding, (B, N)
    ({'mask': ('bool', (2, 1, 1, 197))}, True),                        # key padding, (B, 1, 1, N)
    ({'mask': ('bool', (2, 1, 197, 197))}, False),                     # a per-query mask
    ({'mask': ('float32', (2, 1, 1, 197))}, False),                    # an additive mask
    ({'dropout_p': 0.1}, False),
    ({'softmax_dtype': jnp.bfloat16}, False),                          # the instance's softmax policy
    ({'policy': 'bfloat16'}, False),                                   # the process's
    ({'fused': (True, False)}, False),                                 # the default on a CPU backend
    ({'fused': (False, False)}, False),
    ({'exportable': True}, False),
    ({'mesh': 'one_device'}, True),
    ({'mesh': 'two_devices'}, True),                                   # data axes: under `shard_map`, a device its images
    ({'mesh': 'two_devices', 'batch': 3}, False),                      # a batch the devices do not divide
    ({'mesh': 'model_axis'}, False),                                   # heads split over 'model' keep `_sdpa`
    ({'heads': 3}, False),                                             # 3 x 64: the last column would hold a k head
    ({'heads': 6}, True),
    ({'head_dim': 128, 'heads': 3}, True),
    ({'head_dim': 48, 'heads': 8}, False),                             # 48 does not divide 128
    ({'head_dim': 32, 'heads': 4}, True),
    ({'seq': 1024, 'heads': 16}, True),                                # ViT-L's heads at the longest length, bfloat16
    ({'seq': 1024, 'heads': 16, 'itemsize': 4}, False),                # the same in float32: past the kernels' VMEM limit
], ids=lambda v: '-'.join(f'{k}={x}' for k, x in v.items()).replace(' ', '') if isinstance(v, dict) else str(v))
def test_which_calls_take_the_kernel_pair(change, taken):
    """The predicate, a refusal a case: length, mask kind, dropout, softmax policy, backend, mesh, head width, VMEM."""
    from timm_tpu.layers import config as layer_config
    from timm_tpu.parallel import create_mesh
    from timm_tpu.parallel import mesh as mesh_mod
    case = dict(_TAKEN, **change)
    mask = case['mask'] and jnp.ones(case['mask'][1], case['mask'][0])
    mesh = {None: None, 'one_device': lambda: create_mesh(devices=jax.devices()[:1]),
            'two_devices': lambda: create_mesh(devices=jax.devices()[:2]),
            'model_axis': lambda: create_mesh(devices=jax.devices()[:2], tp=2)}[case['mesh']]
    saved = layer_config._USE_FUSED_ATTN, mesh_mod._GLOBAL_MESH
    try:
        layer_config.set_fused_attn(*case['fused'])
        mesh_mod._GLOBAL_MESH = mesh and mesh()
        with layer_config.set_exportable(case['exportable']), layer_config.set_softmax_dtype(case['policy']):
            got = flash_attention_supported(case['batch'], case['seq'], case['heads'], case['head_dim'], mask,
                                            dropout_p=case['dropout_p'], softmax_dtype=case['softmax_dtype'],
                                            itemsize=case['itemsize'])
    finally:
        layer_config._USE_FUSED_ATTN, mesh_mod._GLOBAL_MESH = saved
    assert got is taken
    shapes_alone = packed_attention_supported(case['batch'], case['seq'], case['heads'], case['head_dim'], mask,
                                              case['itemsize'])
    assert shapes_alone or not got


@pytest.mark.parametrize('mesh_kwargs', [dict(devices=4), dict(devices=8, fsdp=2)], ids=['data4', 'data4_fsdp2'])
def test_packed_pair_under_a_data_mesh_runs_a_device_its_own_images(mesh_kwargs):
    """Several devices under the global mesh and no 'model' axis: the pair runs under `shard_map` over the batch
    axes and gives what one device gives, output and gradient, sharded as its input."""
    from jax.sharding import NamedSharding, PartitionSpec
    from timm_tpu.parallel import create_mesh
    from timm_tpu.parallel import mesh as mesh_mod
    B, N, H, D = 8, 37, 4, 64
    qkv = _rand((B, N, 3 * H * D), 0) * 0.5
    mask = jnp.asarray(np.random.RandomState(1).rand(B, N) > 0.3)
    step = jax.jit(jax.value_and_grad(lambda x: (packed_attention(x, H, mask) ** 2).sum()))
    saved = mesh_mod._GLOBAL_MESH
    try:
        mesh_mod._GLOBAL_MESH = None
        want, want_grad = step(qkv)
        kwargs = dict(mesh_kwargs, devices=jax.devices()[:mesh_kwargs['devices']])
        mesh = mesh_mod._GLOBAL_MESH = create_mesh(**kwargs)
        split = NamedSharding(mesh, PartitionSpec(tuple(mesh.axis_names)))
        got, got_grad = jax.jit(jax.value_and_grad(lambda x: (packed_attention(x, H, mask) ** 2).sum()))(
            jax.device_put(qkv, split))
    finally:
        mesh_mod._GLOBAL_MESH = saved
    assert got_grad.sharding.is_equivalent_to(split, 3)
    assert abs(float(got) - float(want)) < 1e-3 * abs(float(want))
    assert float(jnp.abs(got_grad - want_grad).max()) < 1e-5


@pytest.mark.parametrize('qk_norm', [False, True], ids=['plain_heads', 'qk_norm'])
def test_attention_on_the_kernel_pair_equals_the_plain_path_in_output_and_parameter_gradients(qk_norm, monkeypatch):
    """`Attention` under `set_fused_attn(True, experimental=True)` (the pair, interpreted here) against the
    same module on `_sdpa`; the two host counters say which core a call took."""
    from timm_tpu.layers import Attention, LayerNorm
    from timm_tpu.layers import config as layer_config
    from timm_tpu.parallel import mesh as mesh_mod
    from timm_tpu.utils import tracing
    monkeypatch.setattr(mesh_mod, '_GLOBAL_MESH', None)      # one device: a session's 8-device mesh would not divide 2 images
    attn = Attention(256, num_heads=4, qkv_bias=True, qk_norm=qk_norm, norm_layer=LayerNorm if qk_norm else None,
                     rngs=nnx.Rngs(0))
    x = _rand((2, 37, 256), 5)
    mask = jnp.asarray(np.random.RandomState(6).rand(2, 37) > 0.3)[:, None, None, :]
    loss = lambda m, x, mask: (m(x, attn_mask=mask) ** 2).sum()  # noqa: E731

    def counted(fn):
        before = tracing.snapshot()['counters']
        out = fn()
        after = tracing.snapshot()['counters']
        return out, tuple(after.get(k, 0) - before.get(k, 0) for k in ('attention.fused_calls', 'attention.plain_calls'))

    saved = layer_config._USE_FUSED_ATTN
    try:
        layer_config.set_fused_attn(True, experimental=True)
        (out, grads), fused_counts = counted(lambda: (attn(x), nnx.grad(loss)(attn, x, mask)))
        layer_config.set_fused_attn(True)
        (ref, ref_grads), plain_counts = counted(lambda: (attn(x), nnx.grad(loss)(attn, x, mask)))
    finally:
        layer_config._USE_FUSED_ATTN = saved
    assert fused_counts == (2, 0) and plain_counts == (0, 2)
    assert float(jnp.abs(out - ref).max()) < 1e-5
    leaves, ref_leaves = jax.tree.leaves(grads), jax.tree.leaves(ref_grads)
    assert len(leaves) == len(ref_leaves) == (8 if qk_norm else 4)
    for a, b in zip(leaves, ref_leaves):
        assert float(jnp.abs(a - b).max()) < 1e-4 * max(1.0, float(jnp.abs(b).max()))


# ---- 2. registry ------------------------------------------------------------
# The every-module-registered-or-waived lint is now the analysis rule
# `kernel-registered` (timm_tpu/analysis/source_rules.py); the
# `# no-kernel-registry: <reason>` waiver spelling is unchanged.


def test_registry_portfolio_and_dup_rejection():
    assert registry.kernel_names() == ('causal_flash_attention', 'flash_attention')
    with pytest.raises(ValueError, match='already registered'):
        registry.register(registry.get('flash_attention'))
    with pytest.raises(ValueError, match='regime is empty'):
        dataclasses.replace(registry.get('flash_attention'), name='empty', cases=())


# ---- 3. auto-generated parity (one test per declared regime case) -----------

_PARITY_GRID = harness.parity_cases()


@pytest.mark.parametrize(
    'spec,case', _PARITY_GRID,
    ids=[f'{s.name}-{c.name}' for s, c in _PARITY_GRID])
def test_kernel_parity(spec, case):
    rec = harness.parity_check(spec, case)
    assert rec['ok'], (
        f"{rec['kernel']}/{rec['case']}: max abs err {rec['max_abs_err']:.3g} "
        f"> tol {rec['tol']:.3g}")


# ---- 4. the code chooses the kernel -----------------------------------------

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reads_environment(path):
    """Does the module mention `os.environ` or `os.getenv` anywhere (an `ast` walk: strings and comments do not count)."""
    with open(path) as f:
        tree = ast.parse(f.read())
    return any(isinstance(node, ast.Attribute) and node.attr in ('environ', 'getenv') for node in ast.walk(tree))


def _no_constructor_argument():
    from timm_tpu.data.device_augment import DeviceAugment
    from timm_tpu.task import TrainingTask
    for cls, gone in ((TrainingTask, 'fused_update'), (DeviceAugment, 'fused_epilogue')):
        assert gone not in inspect.signature(cls.__init__).parameters, (cls.__name__, gone)


def _no_command_line_option():
    import train
    with pytest.raises(SystemExit):
        train.make_parser().parse_args(['--fused-update'])


def _no_environment_read():
    pkg = os.path.join(REPO_ROOT, 'timm_tpu')
    paths = [os.path.join(pkg, 'kernels', name) for name in sorted(os.listdir(os.path.join(pkg, 'kernels')))
             if name.endswith('.py')]
    paths += [os.path.join(pkg, 'data', 'device_augment.py'), os.path.join(pkg, 'optim', '_optim_factory.py')]
    assert not [p for p in paths if _reads_environment(p)]


@pytest.mark.parametrize('surface', [_no_constructor_argument, _no_command_line_option, _no_environment_read],
                         ids=['constructor', 'command_line', 'environment'])
def test_nothing_a_user_sets_selects_a_kernel(surface):
    surface()
