"""What the model-zoo test files share: the registry filters that size the
sweeps, the small-model constructor, and the bodies of the forward and the
gradient case. The sweeps sit in six files so that `--dist loadfile` can hand
them to different workers: `test_models_forward.py` / `_forward_conv.py`,
`test_models_backward.py` / `_backward_conv.py`, `test_models_cfg.py`,
`test_models.py`.
"""
from fnmatch import fnmatch

import jax
import jax.numpy as jnp
import numpy as np
from flax import nnx

import timm_tpu
from timm_tpu.models import get_pretrained_cfg, list_models

# size-capped like the reference (_get_input_size, EXCLUDE filters :79-113);
# the default (fast) forward sweep covers small per-family representatives,
# the full registry sweep runs under -m slow (reference shards this across CI)
FAST_FILTERS = [
    'test_*', 'vit_tiny*', 'vit_small_patch32*', '*_atto', '*_femto', '*_pico',
    'resnet18', 'resnet26', 'mixer_s32*', 'efficientnet_b0',
]
EXCLUDE_FILTERS = [
    '*_large*', '*_huge*', '*so400m*', '*_384', '*_giant*', '*_gigantic*', '*_xlarge*',
    'resnet101*', 'resnet152*', 'wide_resnet*', 'efficientnetv2_m*', 'mixer_l*',
    '*x4_clip*', '*x16_clip*', '*x64_clip*', 'repvgg_d2se', 'repvgg_b3*',
    'bat_*',  # BAT bilinear attn needs 256px inputs (block_size 8 divisibility)
]
TEST_MODELS = list_models(filter=FAST_FILTERS)
ALL_MODELS = list_models(exclude_filters=EXCLUDE_FILTERS)
SLOW_MODELS = [m for m in ALL_MODELS if m not in TEST_MODELS]
FWD_SIZE = 64
# the convolutional families of the fast lists go to the `_conv` files, everything else (attention,
# mixers, hybrids) to the others: a model no pattern names still runs, in the second half
CONV_FILTERS = [
    'test_byobnet', 'test_convnext*', 'test_efficientnet*', 'test_mambaout', 'test_nfnet', 'test_regnet',
    'test_resnet', 'convnext*', 'efficientnet*', 'mambaout*', 'resnet*',
]


def split_conv(names):
    """(convolutional families, the rest) of `names`."""
    conv = [n for n in names if any(fnmatch(n, p) for p in CONV_FILTERS)]
    return conv, [n for n in names if n not in conv]


def create_small(model_name, **kwargs):
    cfg = get_pretrained_cfg(model_name)
    try:
        return timm_tpu.create_model(model_name, img_size=FWD_SIZE, num_classes=10, **kwargs), FWD_SIZE
    except TypeError:
        return timm_tpu.create_model(model_name, num_classes=10, **kwargs), (cfg.input_size[-1] if cfg else 224)


def forward_case(model_name, rows=2):
    model, size = create_small(model_name)
    model.eval()
    x = jnp.asarray(np.random.rand(rows, size, size, 3), jnp.float32)
    out = model(x)
    assert out.shape == (rows, 10)
    assert bool(jnp.isfinite(out).all()), 'Output contains NaN/Inf'


def backward_case(model_name):
    model, size = create_small(model_name)
    model.train()
    x = jnp.asarray(np.random.rand(2, size, size, 3), jnp.float32)
    t = jnp.asarray([0, 1])

    def loss_fn(model):
        out = model(x)
        return jnp.mean((out - jax.nn.one_hot(t, out.shape[-1])) ** 2)

    grads = nnx.grad(loss_fn)(model)
    num_params = len(jax.tree.leaves(nnx.state(model, nnx.Param)))
    num_grads = len([g for g in jax.tree.leaves(grads) if g is not None])
    assert num_params == num_grads, 'Some params missing gradients'
    for g in jax.tree.leaves(grads):
        assert bool(jnp.isfinite(g).all()), 'NaN/Inf gradient'
