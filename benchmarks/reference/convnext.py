"""Plain reference of ConvNeXt (arXiv:2201.03545, section 2 and Appendix A),
in `jax.numpy` float32: a 4x4 stride-4 "patchify" stem + LayerNorm, four
stages of blocks (7x7 depthwise convolution, LayerNorm, 1x1 expansion by 4,
GELU, 1x1 projection, LayerScale, stochastic depth, residual), a LayerNorm +
2x2 stride-2 convolution between stages, global average pool, LayerNorm,
linear head.

As the paper's code has them: LayerNorm eps 1e-6 over channels; LayerScale
initialised to `ls_init_value` (1e-6); stochastic-depth rates ramp linearly
over all blocks of all stages. No departure is known.

Imports nothing of the program. Parameters are a flat dict of dotted names.
"""
from __future__ import annotations

from . import ops

LN_EPS = 1e-6


def init_spec(cfg) -> dict:
    """name -> (shape, kind), kinds as in `reference.vit.init_spec`."""
    depths, dims, k = cfg['depths'], cfg['dims'], cfg['kernel_size']
    spec = {
        'stem_conv.kernel': ((4, 4, cfg['in_chans'], dims[0]), 'normal'), 'stem_conv.bias': ((dims[0],), 'normal'),
        'stem_norm.scale': ((dims[0],), 'ones'), 'stem_norm.bias': ((dims[0],), 'normal'),
        'head.norm.scale': ((dims[-1],), 'ones'), 'head.norm.bias': ((dims[-1],), 'normal'),
        'head.fc.kernel': ((dims[-1], cfg['num_classes']), 'normal'), 'head.fc.bias': ((cfg['num_classes'],), 'normal'),
    }
    for s, (depth, dim) in enumerate(zip(depths, dims)):
        if s > 0:
            spec.update({
                f'stages.{s}.downsample_norm.scale': ((dims[s - 1],), 'ones'),
                f'stages.{s}.downsample_norm.bias': ((dims[s - 1],), 'normal'),
                f'stages.{s}.downsample_conv.kernel': ((2, 2, dims[s - 1], dim), 'normal'),
                f'stages.{s}.downsample_conv.bias': ((dim,), 'normal'),
            })
        hidden = int(cfg['mlp_ratio'] * dim)
        for i in range(depth):
            b = f'stages.{s}.blocks.{i}.'
            spec.update({
                b + 'conv_dw.kernel': ((k, k, 1, dim), 'normal'), b + 'conv_dw.bias': ((dim,), 'normal'),
                b + 'norm.scale': ((dim,), 'ones'), b + 'norm.bias': ((dim,), 'normal'),
                b + 'mlp.fc1.kernel': ((dim, hidden), 'normal'), b + 'mlp.fc1.bias': ((hidden,), 'normal'),
                b + 'mlp.fc2.kernel': ((hidden, dim), 'normal'), b + 'mlp.fc2.bias': ((dim,), 'normal'),
                b + 'ls.gamma': ((dim,), float(cfg['ls_init_value'])),
            })
    return spec


def no_weight_decay(name: str) -> bool:
    return False


DROP_PATH_NDIM = 4  # stochastic depth acts on (B, H, W, C)


def drop_path_rates(cfg) -> dict:
    """name of each stochastic-depth site -> its rate (sites of rate 0 left out)."""
    rates = iter(ops.drop_path_rates(cfg.get('drop_path_rate', 0.0), sum(cfg['depths'])))
    named = {f'stages.{s}.blocks.{i}.drop_path': next(rates)
             for s, depth in enumerate(cfg['depths']) for i in range(depth)}
    return {k: r for k, r in named.items() if r > 0}


def forward(cfg, params, x, keep_rows=None, precision: str = 'float32'):
    """Logits (B, classes) of NHWC images `x`. `keep_rows` maps a site of
    `drop_path_rates` to that step's draw (training); None is evaluation."""
    depths, dims, k = cfg['depths'], cfg['dims'], cfg['kernel_size']
    rates = drop_path_rates(cfg)
    keep_rows = keep_rows or {}
    pad = k // 2

    x = ops.conv(x, params['stem_conv.kernel'], 4, 'VALID', precision) + params['stem_conv.bias']
    x = ops.layer_norm(x, params['stem_norm.scale'], params['stem_norm.bias'], LN_EPS)
    for s, (depth, dim) in enumerate(zip(depths, dims)):
        if s > 0:
            p = f'stages.{s}.downsample_'
            x = ops.layer_norm(x, params[p + 'norm.scale'], params[p + 'norm.bias'], LN_EPS)
            x = ops.conv(x, params[p + 'conv.kernel'], 2, 'VALID', precision) + params[p + 'conv.bias']
        for i in range(depth):
            b = f'stages.{s}.blocks.{i}.'
            h = ops.conv(x, params[b + 'conv_dw.kernel'], 1, ((pad, pad), (pad, pad)), precision,
                         groups=dim) + params[b + 'conv_dw.bias']
            h = ops.layer_norm(h, params[b + 'norm.scale'], params[b + 'norm.bias'], LN_EPS)
            h = ops.gelu(ops.matmul(h, params[b + 'mlp.fc1.kernel'], precision) + params[b + 'mlp.fc1.bias'])
            h = ops.matmul(h, params[b + 'mlp.fc2.kernel'], precision) + params[b + 'mlp.fc2.bias']
            h = h * params[b + 'ls.gamma']
            x = x + ops.drop_path(h, keep_rows.get(b + 'drop_path'), rates.get(b + 'drop_path', 0.0))
    x = x.mean(axis=(1, 2))
    x = ops.layer_norm(x, params['head.norm.scale'], params['head.norm.bias'], LN_EPS)
    return ops.matmul(x, params['head.fc.kernel'], precision) + params['head.fc.bias']
