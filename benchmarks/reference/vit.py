"""Plain reference of the Vision Transformer (arXiv:2010.11929, section 3.1
and Table 1), in `jax.numpy` float32: patch embedding, class token + learned
position embedding, `depth` pre-norm blocks of multi-head self-attention and a
GELU MLP, final LayerNorm, linear head on the class token.

Departures from the paper, each as the timm recipe trains it: LayerNorm eps
1e-6; the q, k, v projections are one fused matrix whose output splits as
(3, heads, head_dim); stochastic depth on both residual branches with a linear
ramp of rates (DeiT, arXiv:2012.12877).

Imports nothing of the program. Parameters are a flat dict of dotted names
(`blocks.3.attn.qkv.kernel`), the names `init_spec` gives.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ops

LN_EPS = 1e-6


def _sizes(cfg):
    grid = cfg['img_size'] // cfg['patch_size']
    return cfg['embed_dim'], cfg['depth'], grid * grid + 1, int(cfg['embed_dim'] * cfg['mlp_ratio'])


def init_spec(cfg) -> dict:
    """name -> (shape, kind); kind is 'normal' (std 0.02), 'ones' (1 + normal
    std 0.02) or a float constant. Biases are drawn too: a zero bias would hide
    a missing one."""
    d, depth, tokens, hidden = _sizes(cfg)
    p, c, classes = cfg['patch_size'], cfg['in_chans'], cfg['num_classes']
    spec = {
        'patch_embed.proj.kernel': ((p, p, c, d), 'normal'), 'patch_embed.proj.bias': ((d,), 'normal'),
        'cls_token': ((1, 1, d), 'normal'), 'pos_embed': ((1, tokens, d), 'normal'),
        'norm.scale': ((d,), 'ones'), 'norm.bias': ((d,), 'normal'),
        'head.kernel': ((d, classes), 'normal'), 'head.bias': ((classes,), 'normal'),
    }
    for i in range(depth):
        b = f'blocks.{i}.'
        spec.update({
            b + 'norm1.scale': ((d,), 'ones'), b + 'norm1.bias': ((d,), 'normal'),
            b + 'attn.qkv.kernel': ((d, 3 * d), 'normal'), b + 'attn.qkv.bias': ((3 * d,), 'normal'),
            b + 'attn.proj.kernel': ((d, d), 'normal'), b + 'attn.proj.bias': ((d,), 'normal'),
            b + 'norm2.scale': ((d,), 'ones'), b + 'norm2.bias': ((d,), 'normal'),
            b + 'mlp.fc1.kernel': ((d, hidden), 'normal'), b + 'mlp.fc1.bias': ((hidden,), 'normal'),
            b + 'mlp.fc2.kernel': ((hidden, d), 'normal'), b + 'mlp.fc2.bias': ((d,), 'normal'),
        })
    return spec


def no_weight_decay(name: str) -> bool:
    """AdamW decays matrices only (DeiT's recipe): not biases, norms, the class
    token or the position embedding."""
    return name in ('cls_token', 'pos_embed')


DROP_PATH_NDIM = 3  # stochastic depth acts on (B, tokens, d)


def drop_path_rates(cfg) -> dict:
    """name of each stochastic-depth site -> its rate (sites of rate 0 left out)."""
    rates = ops.drop_path_rates(cfg.get('drop_path_rate', 0.0), cfg['depth'])
    return {f'blocks.{i}.drop_path{j}': r for i, r in enumerate(rates) for j in (1, 2) if r > 0}


def forward(cfg, params, x, keep_rows=None, precision: str = 'float32'):
    """Logits (B, classes) of NHWC images `x`. `keep_rows` maps a site of
    `drop_path_rates` to that step's draw (training); None is evaluation."""
    d, depth, tokens, _ = _sizes(cfg)
    heads, p = cfg['num_heads'], cfg['patch_size']
    rates = drop_path_rates(cfg)
    keep_rows = keep_rows or {}
    B = x.shape[0]

    x = ops.conv(x, params['patch_embed.proj.kernel'], p, 'VALID', precision) + params['patch_embed.proj.bias']
    x = x.reshape(B, tokens - 1, d)
    cls = jnp.broadcast_to(params['cls_token'], (B, 1, d))
    x = jnp.concatenate([cls, x], axis=1) + params['pos_embed']
    for i in range(depth):
        b = f'blocks.{i}.'
        h = ops.layer_norm(x, params[b + 'norm1.scale'], params[b + 'norm1.bias'], LN_EPS)
        qkv = ops.matmul(h, params[b + 'attn.qkv.kernel'], precision) + params[b + 'attn.qkv.bias']
        q, k, v = qkv.reshape(B, tokens, 3, heads, d // heads).transpose(2, 0, 3, 1, 4)
        scores = ops.einsum('bhqd,bhkd->bhqk', q * (d // heads) ** -0.5, k, precision)
        attn = ops.einsum('bhqk,bhkd->bhqd', jax.nn.softmax(scores, axis=-1), v, precision)
        attn = attn.transpose(0, 2, 1, 3).reshape(B, tokens, d)
        attn = ops.matmul(attn, params[b + 'attn.proj.kernel'], precision) + params[b + 'attn.proj.bias']
        x = x + ops.drop_path(attn, keep_rows.get(b + 'drop_path1'), rates.get(b + 'drop_path1', 0.0))
        h = ops.layer_norm(x, params[b + 'norm2.scale'], params[b + 'norm2.bias'], LN_EPS)
        h = ops.gelu(ops.matmul(h, params[b + 'mlp.fc1.kernel'], precision) + params[b + 'mlp.fc1.bias'])
        h = ops.matmul(h, params[b + 'mlp.fc2.kernel'], precision) + params[b + 'mlp.fc2.bias']
        x = x + ops.drop_path(h, keep_rows.get(b + 'drop_path2'), rates.get(b + 'drop_path2', 0.0))
    x = ops.layer_norm(x, params['norm.scale'], params['norm.bias'], LN_EPS)
    return ops.matmul(x[:, 0], params['head.kernel'], precision) + params['head.bias']

