"""Plain reference of LFM2-8B-A1B (`lfm2_moe`; config.json of LiquidAI/LFM2-8B-A1B),
in `jax.numpy` float32 at `Precision.HIGHEST`: a token embedding, pre-norm layers
whose sequence mixer is a gated short convolution or grouped-query attention
(`layer_types`), whose feed-forward is a dense SwiGLU in the first
`num_dense_layers` layers and sigmoid-routed sparse SwiGLU experts with a
selection bias after them, a final RMSNorm, and an output head that IS the
embedding (tied). The loss is next-token cross-entropy; no multi-token
prediction, no auxiliary loss.

Layer l of h (S, d), every linear map without bias, eps = `norm_eps` everywhere:
  a = RMSNorm_1(h)
  'conv':            (b, c, u) = split of a W_in into three of d, in that order; z = b * u;
                     m_t = w_0 z_{t-2} + w_1 z_{t-1} + w_2 z_t (z_t = 0 before the sequence; w: (d, 3), a tap a channel);
                     h' = h + (c * m) W_out
  'full_attention':  q, k, v = a W_q, a W_k, a W_v (32 heads of 64 on 8); q and k through an RMSNorm over the 64
                     (one learned scale for all query heads, one for all key heads), then the rotary turn;
                     h' = h + softmax(q k^T / 8 + causal mask) v W_o, query head g on key/value head g // 4
  e = RMSNorm_2(h')
  l < num_dense_layers:  h_out = h' + (silu(e W_1) * (e W_3)) W_2
  else:                  s = sigmoid(e W_g); idx = top-k(s + bias); p = s[idx] / (sum s[idx] + 1e-6) * scaling;
                         h_out = h' + sum over chosen experts HELD HERE of p_e (silu(e W_1e) * (e W_3e)) W_2e
  logits = RMSNorm_f(h) E^T, E the embedding's own rows.

Given ONE CHIP'S SHARE exactly as the program is: `experts_held` routed experts
from `expert_offset` (the router scores all `num_experts`, the weights stay
normalised over all chosen, and what experts held elsewhere would add is left
out), `vocab_held` rows of the embedding.

What `config.json` does not settle, and what is taken here (the configuration's
file lists each under `assumed`): embedding and head tied; the published
module's `Conv1d(d, d, 3, groups=d, padding=2)` cut to its first S outputs, so
the LAST tap meets the current position; rotary dimensions pair as halves, all 64
turn; the selection bias `expert_bias` is a fixed buffer given in `cfg` (one
vector for every expert layer; zero where none is given): no gradient, no
update in the step; causal attention over the whole sequence, no document
boundaries (which would cut the taps too).

It computes in blocks so that a sequence of 8192 fits beside the weights: every
layer, every block of queries and every chunk of the head is rematerialised in
the backward pass. That changes no value. Imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ops

IGNORE = -1
HEAD_CHUNK = 4096
TOPK_NORM_EPS = 1e-6


def init_spec(cfg) -> dict:
    """name -> (shape, kind): matrices (the taps among them) 'normal' (std 0.02), norm scales 'ones' (1 + normal)."""
    d, hd = cfg['hidden_size'], cfg['head_dim']
    heads, kv = cfg['num_attention_heads'], cfg['num_key_value_heads']
    held, hidden, wide = cfg['experts_held'], cfg['moe_intermediate_size'], cfg['intermediate_size']
    spec = {'embed.embedding': ((cfg['vocab_held'], d), 'normal'), 'norm.scale': ((d,), 'ones')}
    for i, kind in enumerate(cfg['layer_types'][:cfg['num_hidden_layers']]):
        b = f'blocks.{i}.'
        spec.update({b + 'norm1.scale': ((d,), 'ones'), b + 'norm2.scale': ((d,), 'ones')})
        if kind == 'conv':
            spec.update({b + 'conv.in_proj.kernel': ((d, 3 * d), 'normal'), b + 'conv.taps': ((d, cfg['conv_L_cache']), 'normal'),
                         b + 'conv.out_proj.kernel': ((d, d), 'normal')})
        else:
            spec.update({b + 'attn.q_proj.kernel': ((d, heads * hd), 'normal'), b + 'attn.k_proj.kernel': ((d, kv * hd), 'normal'),
                         b + 'attn.v_proj.kernel': ((d, kv * hd), 'normal'), b + 'attn.proj.kernel': ((heads * hd, d), 'normal'),
                         b + 'attn.q_norm.scale': ((hd,), 'ones'), b + 'attn.k_norm.scale': ((hd,), 'ones')})
        if i < cfg['num_dense_layers']:
            spec.update({b + 'mlp.fc1_g.kernel': ((d, wide), 'normal'), b + 'mlp.fc1_x.kernel': ((d, wide), 'normal'),
                         b + 'mlp.fc2.kernel': ((wide, d), 'normal')})
        else:
            spec.update({b + 'mlp.router': ((d, cfg['num_experts']), 'normal'),
                         b + 'mlp.w_gate': ((held, d, hidden), 'normal'), b + 'mlp.w_up': ((held, d, hidden), 'normal'),
                         b + 'mlp.w_down': ((held, hidden, d), 'normal')})
    return spec


def no_weight_decay(name: str) -> bool:
    """AdamW decays every matrix, the embedding, the expert stacks and the taps among them; norm scales are vectors."""
    return False


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """Rotary turn of (..., S, D): dimension j pairs with j + D/2, frequency theta^(-2j/D), position = index."""
    S, D = x.shape[-2], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def swiglu(x, gate, up, down, precision):
    return ops.matmul(jax.nn.silu(ops.matmul(x, gate, precision)) * ops.matmul(x, up, precision), down, precision)


def short_conv(cfg, p, b, a, precision):
    """The gated short convolution of the normalised input a (S, d): the convolution as K shifted multiplies."""
    S, K = a.shape[0], cfg['conv_L_cache']
    gate_b, gate_c, u = jnp.split(ops.matmul(a, p[b + 'conv.in_proj.kernel'], precision), 3, axis=-1)
    z, w = ops._operand(gate_b * u, precision), ops._operand(p[b + 'conv.taps'], precision)   # a convolution's operands
    back = jnp.pad(z, ((K - 1, 0), (0, 0)))                          # row K - 1 + t holds z_t, rows before it zero
    m = sum(w[:, j] * back[j:j + S] for j in range(K))               # tap j meets z_{t - (K - 1 - j)}
    return ops.matmul(gate_c * m, p[b + 'conv.out_proj.kernel'], precision)


def attention(cfg, p, b, a, precision, block_q):
    """Grouped-query attention of the normalised input a (S, d), causal, in query blocks of `block_q` that are the
    iterations of one `lax.map` (each reads all S keys, masked), so the S x S scores never exist whole."""
    S = a.shape[0]
    H, KV, D, eps = cfg['num_attention_heads'], cfg['num_key_value_heads'], cfg['head_dim'], cfg['norm_eps']
    heads = lambda t, n: t.reshape(S, n, D).transpose(1, 0, 2)  # noqa: E731
    q = heads(ops.matmul(a, p[b + 'attn.q_proj.kernel'], precision), H)
    k = heads(ops.matmul(a, p[b + 'attn.k_proj.kernel'], precision), KV)
    v = heads(ops.matmul(a, p[b + 'attn.v_proj.kernel'], precision), KV)
    q = rope(rms_norm(q, p[b + 'attn.q_norm.scale'], eps), cfg['rope_theta'])
    k = rope(rms_norm(k, p[b + 'attn.k_norm.scale'], eps), cfg['rope_theta'])
    G, scale, bq = H // KV, D ** -0.5, min(block_q, S)
    q = q.reshape(KV, G, S, D)                                       # query head g reads key/value head g // group

    @jax.checkpoint
    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, axis=2)
        s = ops.einsum('hgqd,hkd->hgqk', qb, k, precision) * scale
        seen = jnp.arange(S)[None, :] <= (i * bq + jnp.arange(bq))[:, None]
        return ops.einsum('hgqk,hkd->hgqd', jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1), v, precision)

    out = jax.lax.map(block, jnp.arange(S // bq))                    # (blocks, KV, G, bq, D)
    out = out.transpose(1, 2, 0, 3, 4).reshape(H, S, D)
    return ops.matmul(out.transpose(1, 0, 2).reshape(S, H * D), p[b + 'attn.proj.kernel'], precision)


def routes(cfg, p, b, e):
    """Chosen experts (S, k) of all `num_experts` and their weights: the choice by sigmoid score + bias, the weights
    the chosen SCORES over their sum + 1e-6, scaled; float32 at full precision whatever the matmuls' `precision`
    (the configuration states the router in float32)."""
    s = jax.nn.sigmoid(ops.matmul(e, p[b + 'mlp.router'], 'float32'))
    bias = jnp.asarray(cfg['expert_bias'], jnp.float32) if cfg.get('expert_bias') else 0.0
    _, idx = jax.lax.top_k(s + bias, cfg['num_experts_per_tok'])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx, chosen / (chosen.sum(-1, keepdims=True) + TOPK_NORM_EPS) * cfg['routed_scaling_factor']


def experts(cfg, p, b, e, precision):
    """sum over the held experts of weight x SwiGLU expert(e); -> (y, chosen ids). Every held expert reads every
    token and a token's weight for an expert it did not choose is 0; the weight multiplies the expert's hidden
    activation (the down-projection is linear), and the sum over experts is the contraction of ONE product over
    (expert, hidden): three products a layer (PERF.md section 2 on why not a Python loop over the experts)."""
    idx, w = routes(cfg, p, b, e)
    held = cfg['expert_offset'] + jnp.arange(cfg['experts_held'])
    w_held = jnp.where(idx[:, :, None] == held, w[:, :, None], 0.0).sum(1)                 # (S, held); 0 where not chosen
    gate = ops.einsum('sd,edh->seh', e, p[b + 'mlp.w_gate'], precision)
    up = ops.einsum('sd,edh->seh', e, p[b + 'mlp.w_up'], precision)
    hidden = jax.nn.silu(gate) * up * w_held[:, :, None]
    return ops.einsum('seh,ehd->sd', hidden, p[b + 'mlp.w_down'], precision), idx


def layer(cfg, p, i, x, precision, block_q):
    """-> (x, chosen ids or None where the layer's feed-forward is dense)."""
    b, eps = f'blocks.{i}.', cfg['norm_eps']
    a = rms_norm(x, p[b + 'norm1.scale'], eps)
    if cfg['layer_types'][i] == 'conv':
        x = x + short_conv(cfg, p, b, a, precision)
    else:
        x = x + attention(cfg, p, b, a, precision, block_q)
    e = rms_norm(x, p[b + 'norm2.scale'], eps)
    if i < cfg['num_dense_layers']:
        return x + swiglu(e, p[b + 'mlp.fc1_g.kernel'], p[b + 'mlp.fc1_x.kernel'], p[b + 'mlp.fc2.kernel'], precision), None
    y, idx = experts(cfg, p, b, e, precision)
    return x + y, idx


def cross_entropy_sum(cfg, p, h, target, precision):
    """Summed next-token cross-entropy over the positions whose target is not IGNORE, and the logits: the head is
    the embedding's own rows."""
    logits = ops.einsum('sd,vd->sv', rms_norm(h, p['norm.scale'], cfg['norm_eps']), p['embed.embedding'], precision)
    valid = target != IGNORE
    safe = jnp.where(valid, target, 0)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
    return jnp.where(valid, nll, 0.0).sum(), logits


def forward(cfg, p, ids, target, precision: str = 'float32', block_q: int = 1024):
    """One sequence: ids, target (S,). -> dict of `loss_main_sum` (summed over its valid positions), `n_main`,
    `logits`, `routes` ((expert layers, S, k) chosen ids)."""
    cfg = dict(cfg, layer_types=tuple(cfg['layer_types']))
    run = jax.checkpoint(lambda p, x, i: layer(cfg, p, i, x, precision, block_q), static_argnums=(2,))
    x = p['embed.embedding'][ids]
    chosen = []
    for i in range(cfg['num_hidden_layers']):
        x, idx = run(p, x, i)
        if idx is not None:
            chosen.append(idx)
    head = jax.checkpoint(lambda p, h, t: cross_entropy_sum(cfg, p, h, t, precision))
    parts = [head(p, x[i:i + HEAD_CHUNK], target[i:i + HEAD_CHUNK]) for i in range(0, x.shape[0], HEAD_CHUNK)]
    return {'loss_main_sum': sum(s for s, _ in parts), 'n_main': (target != IGNORE).sum(),
            'logits': jnp.concatenate([l for _, l in parts], axis=0), 'routes': jnp.stack(chosen)}


def loss(cfg, p, ids, target, n_main, n_mtp=None, precision: str = 'float32', block_q: int = 1024):
    """One sequence's share of the batch's loss: its summed cross-entropy over the BATCH's count of valid
    positions (`n_main`; `n_mtp` is `lm_train_step.py`'s and unused: no MTP term). -> (loss share, chosen ids)."""
    out = forward(cfg, p, ids, target, precision, block_q)
    return out['loss_main_sum'] / n_main, out['routes']
