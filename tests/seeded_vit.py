"""The tiny ViT behind the "default path is the plain path" tests, with nothing
of the library's initialiser RNG in its premise: every parameter is filled by
dotted name from a seeded numpy generator, and the expected logits are the
plain float32 reference's (`benchmarks/reference/vit.py`) on the same weights
and the same seeded input. The fill follows `benchmarks/harness/weights.py`
(N(0, 0.02); norm scales 1 + N(0, 0.02); biases drawn too).
"""
import os
import sys

import jax.numpy as jnp
import numpy as np
from flax import nnx

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import timm_tpu  # noqa: E402
from benchmarks.reference import vit as reference  # noqa: E402
from timm_tpu.utils.serialization import flatten_pytree, unflatten_into  # noqa: E402

MODEL, IMG = 'vit_tiny_patch16_224', 64
SIZES = dict(img_size=IMG, patch_size=16, in_chans=3, embed_dim=192, depth=12, num_heads=3,
             mlp_ratio=4, num_classes=1000)
STD = 0.02
# float32 round-off between two orders of the same sums: 6e-7 was seen on logits of range ~±0.90
REFERENCE_TOL = 1e-5


def seeded_input(rows: int = 2) -> np.ndarray:
    """NHWC images in [0, 1): for two rows, the values the tolerances of the tests that take it were set on."""
    return np.random.RandomState(42).rand(rows, IMG, IMG, 3).astype(np.float32)


def seeded_weights(seed: int = 0) -> dict:
    """dotted name -> float32 array, for every name of the reference's `init_spec`."""
    rng = np.random.default_rng(seed)
    out = {}
    for name, (shape, kind) in sorted(reference.init_spec(SIZES).items()):
        w = rng.standard_normal(shape, dtype=np.float32) * STD
        out[name] = w + 1.0 if kind == 'ones' else w
    return out


def build(weights: dict):
    """The library's tiny ViT in eval mode, every parameter overwritten from `weights`."""
    model = timm_tpu.create_model(MODEL, img_size=IMG)
    model.eval()
    params = nnx.state(model, nnx.Param)
    have = {name: leaf.shape for name, leaf in flatten_pytree(params).items()}
    want = {name: w.shape for name, w in weights.items()}
    if have != want:
        odd = sorted(n for n in have.keys() | want.keys() if have.get(n) != want.get(n))
        raise ValueError(f'the reference and the model disagree on {len(odd)} parameters, e.g. {odd[:4]}')
    nnx.update(model, unflatten_into(params, weights))
    return model


def reference_logits(weights: dict, x) -> np.ndarray:
    params = {k: jnp.asarray(v) for k, v in weights.items()}
    return np.asarray(reference.forward(SIZES, params, jnp.asarray(x)))
