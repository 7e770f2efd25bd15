"""Layer unit tests (reference: tests/test_layers.py, test_layers_drop.py,
test_layers_pool.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import nnx

from timm_tpu.layers import (
    Attention, DropPath, LayerScale, Mlp, PatchEmbed, SelectAdaptivePool2d,
    calculate_drop_path_rates, get_act_fn, get_norm_layer, global_pool_nlc,
    resample_abs_pos_embed,
)


def test_act_factory():
    for name in ('relu', 'gelu', 'silu', 'hard_swish', 'mish', 'quick_gelu', 'gelu_tanh'):
        fn = get_act_fn(name)
        out = fn(jnp.asarray([-1.0, 0.0, 1.0]))
        assert out.shape == (3,)
    assert get_act_fn(None) is None
    with pytest.raises(ValueError):
        get_act_fn('bogus')


def test_norm_factory():
    rngs = nnx.Rngs(0)
    for name in ('layernorm', 'rmsnorm', 'groupnorm', 'batchnorm2d', 'simplenorm'):
        cls = get_norm_layer(name)
        layer = cls(64, rngs=rngs)
        out = layer(jnp.ones((2, 4, 4, 64)))
        assert out.shape == (2, 4, 4, 64)


def test_attention_shapes_and_mask():
    rngs = nnx.Rngs(0)
    attn = Attention(64, num_heads=4, qkv_bias=True, rngs=rngs)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 10, 64), jnp.float32)
    out = attn(x)
    assert out.shape == (2, 10, 64)
    # boolean mask: masked key contributes nothing
    mask = jnp.ones((2, 1, 10, 10), bool).at[:, :, :, -1].set(False)
    out_masked = attn(x, attn_mask=mask)
    x_zeroed = x.at[:, -1].set(1e9)  # huge value in masked slot must not leak
    attn_out2 = attn(x_zeroed, attn_mask=mask)
    assert bool(jnp.allclose(out_masked[:, :-1], attn_out2[:, :-1], atol=1e-3))


def test_attention_qk_norm():
    from timm_tpu.layers import LayerNorm
    rngs = nnx.Rngs(0)
    attn = Attention(64, num_heads=4, qk_norm=True, norm_layer=LayerNorm, rngs=rngs)
    assert attn(jnp.ones((1, 5, 64))).shape == (1, 5, 64)


def test_drop_path_stats():
    rngs = nnx.Rngs(dropout=0)
    dp = DropPath(0.5, rngs=rngs)
    dp.train()
    x = jnp.ones((512, 4))
    out = dp(x)
    kept = float((out[:, 0] != 0).mean())
    assert 0.35 < kept < 0.65  # ~keep_prob
    # kept rows scaled by 1/keep_prob
    nz = np.asarray(out[out[:, 0] != 0])
    assert np.allclose(nz, 2.0)
    dp.eval()
    assert bool(jnp.allclose(dp(x), x))


def test_drop_path_rates():
    rates = calculate_drop_path_rates(0.3, 4)
    assert rates[0] == 0.0 and rates[-1] == pytest.approx(0.3)
    stage = calculate_drop_path_rates(0.3, [2, 2], stagewise=True)
    assert len(stage) == 2 and stage[1][1] == pytest.approx(0.3)


def test_patch_embed():
    rngs = nnx.Rngs(0)
    pe = PatchEmbed(img_size=32, patch_size=8, in_chans=3, embed_dim=64, rngs=rngs)
    out = pe(jnp.ones((2, 32, 32, 3)))
    assert out.shape == (2, 16, 64)
    assert pe.grid_size == (4, 4)
    pe2 = PatchEmbed(img_size=None, patch_size=8, embed_dim=64, flatten=False, rngs=rngs)
    assert pe2(jnp.ones((2, 40, 32, 3))).shape == (2, 5, 4, 64)


def test_pos_embed_resample():
    pe = jnp.asarray(np.random.RandomState(0).randn(1, 17, 8), jnp.float32)  # 4x4 + cls
    out = resample_abs_pos_embed(pe, new_size=(8, 8), num_prefix_tokens=1)
    assert out.shape == (1, 65, 8)
    assert bool(jnp.allclose(out[:, 0], pe[:, 0]))  # prefix untouched
    # non-square same-count must NOT no-op
    out2 = resample_abs_pos_embed(pe, new_size=(2, 8), num_prefix_tokens=1)
    assert out2.shape == (1, 17, 8)
    assert not bool(jnp.allclose(out2[:, 1:], pe[:, 1:]))


def test_pooling():
    x = jnp.asarray(np.random.RandomState(0).randn(2, 9, 16), jnp.float32)
    assert global_pool_nlc(x, 'token').shape == (2, 16)
    assert bool(jnp.allclose(global_pool_nlc(x, 'avg', num_prefix_tokens=1), x[:, 1:].mean(1)))
    assert bool(jnp.allclose(global_pool_nlc(x, 'max', num_prefix_tokens=0), x.max(1)))
    g = jnp.asarray(np.random.RandomState(1).randn(2, 4, 4, 16), jnp.float32)
    assert SelectAdaptivePool2d(pool_type='avg')(g).shape == (2, 16)
    assert SelectAdaptivePool2d(pool_type='catavgmax')(g).shape == (2, 32)
    assert SelectAdaptivePool2d(pool_type='')(g).shape == g.shape


def test_mlp_variants():
    from timm_tpu.layers import GluMlp, SwiGLU
    rngs = nnx.Rngs(0)
    x = jnp.ones((2, 5, 32))
    assert Mlp(32, 64, rngs=rngs)(x).shape == (2, 5, 32)
    assert GluMlp(32, 64, rngs=rngs)(x).shape == (2, 5, 32)
    assert SwiGLU(32, 64, rngs=rngs)(x).shape == (2, 5, 32)


def test_layer_scale():
    ls = LayerScale(16, init_values=1e-4, rngs=nnx.Rngs(0))
    x = jnp.ones((2, 3, 16))
    assert bool(jnp.allclose(ls(x), x * 1e-4))


def test_sincos_pos_embed():
    from timm_tpu.layers import build_sincos2d_pos_embed
    emb = build_sincos2d_pos_embed((4, 4), dim=64)
    assert emb.shape == (16, 64)
    assert bool(jnp.isfinite(emb).all())


def test_rotary_embed():
    from timm_tpu.layers import RotaryEmbeddingCat
    from timm_tpu.layers.attention import apply_rot_embed_cat
    rope = RotaryEmbeddingCat(32, in_pixels=False, feat_shape=(4, 4))
    emb = rope.get_embed()
    assert emb.shape == (16, 64)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 4, 16, 32), jnp.float32)
    out = apply_rot_embed_cat(x, emb)
    assert out.shape == x.shape
    # norm-preserving
    assert bool(jnp.allclose(jnp.linalg.norm(out, axis=-1), jnp.linalg.norm(x, axis=-1), atol=1e-3))


def test_clip_grads():
    from timm_tpu.utils import adaptive_clip_grad, clip_grad_norm, clip_grad_value
    grads = {'a': jnp.full((4, 4), 10.0), 'b': jnp.full((4,), -10.0)}
    clipped, norm = clip_grad_norm(grads, 1.0)
    from timm_tpu.utils import global_grad_norm
    assert float(global_grad_norm(clipped)) == pytest.approx(1.0, abs=1e-3)
    clipped, _ = clip_grad_value(grads, 0.5)
    assert float(jnp.max(jnp.abs(clipped['a']))) == 0.5
    params = {'a': jnp.ones((4, 4)), 'b': jnp.ones((4,))}
    agc = adaptive_clip_grad(params, grads, clip_factor=0.01)
    assert float(jnp.abs(jax.tree.leaves(agc)[0]).max()) < 10.0


def test_attn_modules():
    from timm_tpu.layers import CbamModule, EcaModule, create_attn
    rngs = nnx.Rngs(0)
    x = jnp.asarray(np.random.RandomState(0).rand(2, 8, 8, 64), jnp.float32)
    for name in ('se', 'ese', 'eca', 'cbam'):
        mod = create_attn(name, 64, rngs=rngs)
        assert mod(x).shape == x.shape
    assert create_attn(None, 64, rngs=rngs) is None
    with pytest.raises(ValueError):
        create_attn('bogus', 64, rngs=rngs)


def test_blur_pool():
    from timm_tpu.layers import BlurPool2d
    x = jnp.ones((1, 8, 8, 4))
    out = BlurPool2d(4)(x)
    assert out.shape == (1, 4, 4, 4)
    assert bool(jnp.allclose(out, 1.0, atol=1e-5))  # low-pass of constant = constant


def test_scaled_std_conv():
    from timm_tpu.layers import ScaledStdConv2d
    rngs = nnx.Rngs(0)
    conv = ScaledStdConv2d(8, 16, 3, rngs=rngs)
    x = jnp.asarray(np.random.RandomState(0).rand(2, 8, 8, 8), jnp.float32)
    assert conv(x).shape == (2, 8, 8, 16)
    # kernel itself must stay unstandardized (standardization is call-time)
    w = conv.kernel[...]
    assert float(jnp.abs(w.mean(axis=(0, 1, 2))).max()) > 1e-4


def test_evo_norms():
    from timm_tpu.layers import EvoNorm2dB0, EvoNorm2dS0
    rngs = nnx.Rngs(0)
    x = jnp.asarray(np.random.RandomState(0).rand(2, 8, 8, 64), jnp.float32)
    b0 = EvoNorm2dB0(64, rngs=rngs)
    assert b0(x).shape == x.shape
    rv_before = b0.running_var[...].copy()
    b0(x)
    assert not bool(jnp.allclose(rv_before, b0.running_var[...]))  # stats update
    s0 = EvoNorm2dS0(64, rngs=rngs)
    assert s0(x).shape == x.shape


def test_diff_attention_layer():
    from timm_tpu.layers import DiffAttention
    rngs = nnx.Rngs(0)
    attn = DiffAttention(64, num_heads=4, depth=3, rngs=rngs)
    x = jnp.asarray(np.random.RandomState(0).rand(2, 10, 64), jnp.float32)
    out = attn(x)
    assert out.shape == (2, 10, 64)
    assert 0.2 < attn.lambda_init < 0.8


# ---- round-2 layer pack ----------------------------------------------------

def test_rel_pos_bias_shapes():
    from timm_tpu.layers import RelPosBias, RelPosMlp, gen_relative_position_index
    idx = gen_relative_position_index((4, 4))
    assert idx.shape == (16, 16) and idx.max() == 7 * 7 - 1 and idx.min() == 0
    idx_cls = gen_relative_position_index((4, 4), class_token=True)
    assert idx_cls.shape == (17, 17) and idx_cls.max() == 7 * 7 + 2
    rpb = RelPosBias(window_size=(4, 4), num_heads=3, rngs=nnx.Rngs(0))
    bias = rpb.get_bias()
    assert bias.shape == (1, 3, 16, 16)
    # relative bias must be symmetric under query/key swap of identical offsets
    attn = jnp.zeros((2, 3, 16, 16))
    out = rpb(attn)
    assert out.shape == attn.shape
    rpm = RelPosMlp(window_size=(4, 4), num_heads=3, mode='cr', rngs=nnx.Rngs(0))
    assert rpm.get_bias().shape == (1, 3, 16, 16)
    rpm_swin = RelPosMlp(window_size=(4, 4), num_heads=2, mode='swin', rngs=nnx.Rngs(0))
    assert rpm_swin.get_bias().shape == (1, 2, 16, 16)


def test_rel_pos_bias_translation_invariance():
    from timm_tpu.layers import RelPosBias
    rpb = RelPosBias(window_size=(3, 3), num_heads=1, rngs=nnx.Rngs(0))
    b = np.asarray(rpb.get_bias())[0, 0]
    # tokens 0→4 and 4→8 have the same relative offset (1,1): same bias value
    assert b[0, 4] == b[4, 8]
    assert b[1, 5] == b[4, 8]


def test_split_attn():
    from timm_tpu.layers import SplitAttn
    m = SplitAttn(16, radix=2, rngs=nnx.Rngs(0))
    m.eval()
    x = jnp.asarray(np.random.RandomState(0).rand(2, 8, 8, 16), jnp.float32)
    y = m(x)
    assert y.shape == (2, 8, 8, 16)
    m1 = SplitAttn(16, radix=1, rngs=nnx.Rngs(0))
    m1.eval()
    assert m1(x).shape == (2, 8, 8, 16)


def test_selective_kernel():
    from timm_tpu.layers import SelectiveKernel
    m = SelectiveKernel(16, 16, split_input=True, rngs=nnx.Rngs(0))
    m.eval()
    x = jnp.asarray(np.random.RandomState(0).rand(2, 8, 8, 16), jnp.float32)
    assert m(x).shape == (2, 8, 8, 16)


def test_selective_kernel_aa_drop_wired():
    from timm_tpu.layers import BlurPool2d, SelectiveKernel
    from timm_tpu.layers.drop import Dropout
    import functools
    m = SelectiveKernel(
        16, 16, stride=2, split_input=False,
        aa_layer=BlurPool2d,
        drop_layer=functools.partial(Dropout, 0.5, rngs=nnx.Rngs(7)),
        rngs=nnx.Rngs(0))
    # aa pool must actually be attached (conv strides 1, aa strides 2)
    assert all(p.aa is not None for p in m.paths)
    assert all(p.conv.strides == (1, 1) for p in m.paths)
    assert all(p.bn.drop is not None for p in m.paths)
    m.eval()
    x = jnp.asarray(np.random.RandomState(0).rand(2, 8, 8, 16), jnp.float32)
    assert m(x).shape == (2, 4, 4, 16)


def test_gather_excite_and_global_context():
    from timm_tpu.layers import GatherExcite, GlobalContext
    x = jnp.asarray(np.random.RandomState(0).rand(2, 8, 8, 16), jnp.float32)
    for kwargs in (dict(extent=0), dict(extent=2), dict(extent=2, extra_params=True)):
        m = GatherExcite(16, **kwargs, rngs=nnx.Rngs(0))
        m.eval()
        assert m(x).shape == x.shape, kwargs
    gc = GlobalContext(16, rngs=nnx.Rngs(0))
    gc.eval()
    assert gc(x).shape == x.shape
    gca = GlobalContext(16, fuse_add=True, fuse_scale=False, rngs=nnx.Rngs(0))
    gca.eval()
    assert gca(x).shape == x.shape


def test_drop_block_2d_stats():
    from timm_tpu.layers import drop_block_2d
    x = jnp.ones((4, 16, 16, 8))
    key = jax.random.PRNGKey(0)
    y = drop_block_2d(x, key, drop_prob=0.2, block_size=5, scale_by_keep=False)
    dropped = float((y == 0).mean())
    assert 0.05 < dropped < 0.5  # roughly drop_prob worth of area zeroed
    # scale_by_keep keeps the expectation roughly constant
    y2 = drop_block_2d(x, key, drop_prob=0.2, block_size=5, scale_by_keep=True)
    assert abs(float(y2.mean()) - 1.0) < 0.05


def test_split_batchnorm_distinct_stats():
    from timm_tpu.layers import SplitBatchNormAct2d, convert_splitbn_model
    m = SplitBatchNormAct2d(8, num_splits=2, apply_act=False, rngs=nnx.Rngs(0))
    rng = np.random.RandomState(0)
    # first half ~N(0,1), second half ~N(4,1): aux stats should diverge
    x = np.concatenate([rng.randn(8, 4, 4, 8), rng.randn(8, 4, 4, 8) + 4.0]).astype(np.float32)
    m.train()
    m(jnp.asarray(x))
    # one EMA update at momentum 0.1: primary ≈ 0.1*0, aux ≈ 0.1*4
    assert float(m.mean[...].mean()) < 0.1
    assert float(m.aux_bn[0].mean[...].mean()) > 0.25
    # eval uses primary stats on the full batch
    m.eval()
    y = m(jnp.asarray(x))
    assert y.shape == x.shape

    # conversion walks a small model and swaps BN layers in place
    import timm_tpu
    model = timm_tpu.create_model('test_efficientnet', num_classes=10)
    convert_splitbn_model(model, num_splits=2)
    found = []

    def walk(mod):
        for v in vars(mod).values():
            if isinstance(v, SplitBatchNormAct2d):
                found.append(v)
            elif isinstance(v, nnx.List):
                for it in v:
                    if isinstance(it, SplitBatchNormAct2d):
                        found.append(it)
                    elif isinstance(it, nnx.Module):
                        walk(it)
            elif isinstance(v, nnx.Module):
                walk(v)
    walk(model)
    assert found, 'no BN layers converted'


def test_split_batchnorm_plain_no_act():
    from timm_tpu.layers import SplitBatchNorm2d
    m = SplitBatchNorm2d(8, num_splits=2, rngs=nnx.Rngs(0))
    m.train()
    x = jnp.asarray(np.random.RandomState(0).randn(4, 4, 4, 8), jnp.float32)
    y = m(x)
    # plain BN: negative outputs survive (no hidden relu)
    assert float(y.min()) < 0.0


def test_filter_response_norm():
    from timm_tpu.layers import FilterResponseNormAct2d, FilterResponseNormTlu2d
    x = jnp.asarray(np.random.RandomState(0).rand(2, 6, 6, 8) * 3, jnp.float32)
    y = FilterResponseNormAct2d(8, rngs=nnx.Rngs(0))(x)
    assert y.shape == x.shape and float(y.min()) >= 0.0  # relu applied
    y2 = FilterResponseNormTlu2d(8, rngs=nnx.Rngs(0))(x)
    assert y2.shape == x.shape


def test_cond_conv2d_routing():
    from timm_tpu.layers import CondConv2d
    m = CondConv2d(8, 16, 3, num_experts=4, bias=True, rngs=nnx.Rngs(0))
    x = jnp.asarray(np.random.RandomState(0).rand(2, 8, 8, 8), jnp.float32)
    r_a = jax.nn.softmax(jnp.asarray([[1.0, 0, 0, 0], [0, 1.0, 0, 0]]) * 10)
    y = m(x, r_a)
    assert y.shape == (2, 8, 8, 16)
    # different routing → different outputs for the same input
    r_b = jax.nn.softmax(jnp.asarray([[0, 0, 1.0, 0], [0, 0, 0, 1.0]]) * 10)
    assert not np.allclose(np.asarray(y), np.asarray(m(x, r_b)))
    # padding=None resolves like create_conv2d (same-when-stride-1), and
    # unknown strings raise instead of silently meaning VALID
    m2 = CondConv2d(8, 16, 3, padding=None, rngs=nnx.Rngs(0))
    assert m2(x, r_a[:, :4]).shape == (2, 8, 8, 16)
    with pytest.raises(ValueError):
        CondConv2d(8, 16, 3, padding='samee', rngs=nnx.Rngs(0))


def test_mixed_conv2d():
    from timm_tpu.layers import MixedConv2d
    m = MixedConv2d(16, 16, kernel_size=[3, 5], depthwise=True, rngs=nnx.Rngs(0))
    x = jnp.asarray(np.random.RandomState(0).rand(2, 8, 8, 16), jnp.float32)
    assert m(x).shape == (2, 8, 8, 16)


def test_test_time_pool_head():
    import timm_tpu
    from timm_tpu.layers import TestTimePoolHead
    model = timm_tpu.create_model('test_efficientnet', num_classes=10)
    model.eval()
    wrapped = TestTimePoolHead(model, original_pool=2)
    x = jnp.asarray(np.random.RandomState(0).rand(2, 96, 96, 3), jnp.float32)
    out = wrapped(x)
    assert out.shape == (2, 10)


def test_create_attn_new_modules():
    from timm_tpu.layers import create_attn
    x = jnp.asarray(np.random.RandomState(0).rand(2, 8, 8, 16), jnp.float32)
    for name in ('ge', 'gc', 'splat', 'sk'):
        m = create_attn(name, 16, rngs=nnx.Rngs(0))
        m.eval()
        assert m(x).shape == x.shape, name


def test_radix_softmax_cardinality_order():
    """radix weights must be radix-major after flatten so the caller's
    (B, radix, C) reshape picks weights for the right cardinal group."""
    from timm_tpu.layers.split_attn import radix_softmax
    B, card, radix, ch = 1, 2, 2, 3
    logits = jnp.arange(card * radix * ch, dtype=jnp.float32).reshape(1, 1, 1, -1) * 100
    out = radix_softmax(logits, radix, card).reshape(B, radix, card * ch)
    # within each (card, ch) column the two radix entries sum to 1
    sums = np.asarray(out.sum(axis=1))
    assert np.allclose(sums, 1.0, atol=1e-5)


def test_split_attn_groups():
    from timm_tpu.layers import SplitAttn
    m = SplitAttn(16, radix=2, groups=2, rngs=nnx.Rngs(0))
    m.eval()
    x = jnp.asarray(np.random.RandomState(0).rand(2, 8, 8, 16), jnp.float32)
    assert m(x).shape == (2, 8, 8, 16)


@pytest.mark.parametrize('name,index,products', [
    ('glm4_moe_lite_toy', 0, 42), ('glm4_moe_lite_toy', 1, 46), ('lfm2_moe_toy', 0, 27), ('solar_open2_toy', 0, 63),
    ('solar_open2_toy', 1, 127)], ids=['glm-dense', 'glm-shared-expert', 'lfm2-dense', 'solar-gqa-shared', 'solar-kda-shared'])
def test_the_name_on_swiglus_up_products_is_inert_under_a_block_policy_that_does_not_list_it(name, index, products):
    """`SwiGLU` names its two up-products `FFN_UP` (PR 49) and only EvaByte's block policy lists the name. The blocks
    of the three other families that hold a `SwiGLU` (a leading dense layer, a shared expert) run under
    `save_only_these_names(CORE_OUT)`: the `dot_general` equations in one block's gradient, through the model's own
    `_run_block`, are the numbers read on PR 49's parent, before the name existed. Listing the name would take two
    products out of the second forward pass and hold the pair through the whole backward pass, which for a LEADING
    layer is the step's peak (PERF.md section 7): an edit that widens a shared policy shows here, on the CPU."""
    import timm_tpu
    from remat_common import block_grad_dots
    from timm_tpu.layers.latent_attention import CORE_OUT
    from timm_tpu.layers.mlp import FFN_UP
    model = timm_tpu.create_model(name, seed=0)
    assert block_grad_dots(model, index, 32) == products == block_grad_dots(model, index, 32, (CORE_OUT,))
    assert block_grad_dots(model, index, 32, (CORE_OUT, FFN_UP)) == products - 2


# The hard-coded-fp32-softmax lint is now the analysis rule `fp32-softmax`
# (timm_tpu/analysis/source_rules.py), enforced by tests/test_analysis.py.
