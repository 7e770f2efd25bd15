"""Operations a model needs, from its shapes alone: multiply-accumulates of the
forward pass's matrix multiplications and convolutions (biases, norms,
softmax and activations left out, as the papers count them). A training step
needs the forward pass once and twice that for the backward pass, so
`train_flops_per_image` is MACs x 2 x 3 — recomputed operations never count,
and no compiler's `cost_analysis` is asked.

Known values (checked in the tests): ViT-B/16 at 224 is 17.6 GMACs
(arXiv:2010.11929 / timm's table), ConvNeXt-B at 224 is 15.4 GMACs
(arXiv:2201.03545 Table 1).
"""
from __future__ import annotations


def vit_forward_macs(cfg) -> int:
    d, depth, p = cfg['embed_dim'], cfg['depth'], cfg['patch_size']
    patches = (cfg['img_size'] // p) ** 2
    tokens = patches + 1
    hidden = int(d * cfg['mlp_ratio'])
    patch_embed = patches * p * p * cfg['in_chans'] * d
    attention = tokens * d * 3 * d + 2 * tokens * tokens * d + tokens * d * d
    mlp = 2 * tokens * d * hidden
    return patch_embed + depth * (attention + mlp) + d * cfg['num_classes']


def convnext_forward_macs(cfg) -> int:
    k, dims, depths = cfg['kernel_size'], cfg['dims'], cfg['depths']
    side = cfg['img_size'] // 4
    macs = side * side * 4 * 4 * cfg['in_chans'] * dims[0]
    for s, (depth, dim) in enumerate(zip(depths, dims)):
        if s > 0:
            side //= 2
            macs += side * side * 2 * 2 * dims[s - 1] * dim
        hidden = int(cfg['mlp_ratio'] * dim)
        macs += depth * side * side * (k * k * dim + 2 * dim * hidden)
    return macs + dims[-1] * cfg['num_classes']


FORWARD_MACS = {'vit': vit_forward_macs, 'convnext': convnext_forward_macs}


def forward_macs(reference: str, cfg) -> int:
    return FORWARD_MACS[reference](cfg)


def train_flops_per_image(reference: str, cfg) -> int:
    return forward_macs(reference, cfg) * 2 * 3
