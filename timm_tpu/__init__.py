"""timm_tpu — a TPU-native (JAX/XLA/Pallas) image-models framework.

A ground-up re-design of the capabilities of huggingface/pytorch-image-models
for TPU hardware: NHWC layouts, bf16 compute, one jitted train step over a
`jax.sharding.Mesh`, explicit RNG, and Pallas kernels for the hot ops.
"""
__version__ = '0.1.0'

from .layers import *  # noqa: F401,F403
from .models import (  # noqa: F401
    create_model, is_model, list_models, list_modules, list_pretrained,
    model_entrypoint, register_model,
)
