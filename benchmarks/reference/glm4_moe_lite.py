"""Plain reference of GLM-4.7-Flash (`glm4_moe_lite`; config.json of
zai-org/GLM-4.7-Flash), in `jax.numpy` float32 at `Precision.HIGHEST`: token
embedding, pre-norm blocks of multi-head latent attention (DeepSeek-V2,
arXiv:2405.04434 section 2.1) and a feed-forward — a dense SwiGLU in the first
`first_k_dense_replace` layers, after them sigmoid-routed sparse experts with a
shared expert (DeepSeek-V3, arXiv:2412.19437 section 2.1.2, `noaux_tc`) —, a
final RMSNorm, an untied output head, and one multi-token-prediction module
(arXiv:2412.19437 section 2.2). The loss is next-token cross-entropy plus
`mtp_loss_weight` times the module's.

Given ONE CHIP'S SHARE exactly as the program is: `experts_held` routed experts
from `expert_offset` (the router scores all `n_routed_experts`, the weights are
normalised over all chosen, and what experts held elsewhere would add is left
out), `vocab_held` rows of embedding and head.

Departures from the published model, each because `config.json` does not settle
it or the step cannot hold it:
  * the router's `e_score_correction_bias` is a fixed buffer (zero unless given):
    it takes no gradient and its update from the experts' load is not part of the
    step (the public modelling code updates it nowhere; the rate is not in config);
  * the MTP loss weight is 0.3 (DeepSeek-V3's first value) and the module's input
    is [RMSNorm(Emb(t_{i+1})) | RMSNorm(h_i)] in that order;
  * rotary dimensions pair as halves (j with j + 32), not interleaved;
  * causal attention runs over the whole window: no document boundaries.

It computes in blocks so that a sequence of 8192 fits beside the weights: every
layer and every block of queries is rematerialised in the backward pass. That
changes no value. Imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ops

IGNORE = -1


def init_spec(cfg) -> dict:
    """name -> (shape, kind): matrices 'normal' (std 0.02), norm scales 'ones' (1 + normal)."""
    d, heads = cfg['hidden_size'], cfg['num_attention_heads']
    qk = cfg['qk_nope_head_dim'] + cfg['qk_rope_head_dim']
    held, hidden = cfg['experts_held'], cfg['moe_intermediate_size']
    shared = hidden * cfg['n_shared_experts']

    def block(b, dense):
        spec = {
            b + 'norm1.scale': ((d,), 'ones'), b + 'norm2.scale': ((d,), 'ones'),
            b + 'attn.q_a.kernel': ((d, cfg['q_lora_rank']), 'normal'),
            b + 'attn.q_norm.scale': ((cfg['q_lora_rank'],), 'ones'),
            b + 'attn.q_b.kernel': ((cfg['q_lora_rank'], heads * qk), 'normal'),
            b + 'attn.kv_a.kernel': ((d, cfg['kv_lora_rank'] + cfg['qk_rope_head_dim']), 'normal'),
            b + 'attn.kv_norm.scale': ((cfg['kv_lora_rank'],), 'ones'),
            b + 'attn.kv_b.kernel': ((cfg['kv_lora_rank'], heads * (cfg['qk_nope_head_dim'] + cfg['v_head_dim'])), 'normal'),
            b + 'attn.o.kernel': ((heads * cfg['v_head_dim'], d), 'normal'),
        }
        if dense:
            w = cfg['intermediate_size']
            spec.update({b + 'mlp.fc1_g.kernel': ((d, w), 'normal'), b + 'mlp.fc1_x.kernel': ((d, w), 'normal'),
                         b + 'mlp.fc2.kernel': ((w, d), 'normal')})
        else:
            spec.update({b + 'mlp.router': ((d, cfg['n_routed_experts']), 'normal'),
                         b + 'mlp.w_gate': ((held, d, hidden), 'normal'), b + 'mlp.w_up': ((held, d, hidden), 'normal'),
                         b + 'mlp.w_down': ((held, hidden, d), 'normal'),
                         b + 'mlp.shared.fc1_g.kernel': ((d, shared), 'normal'),
                         b + 'mlp.shared.fc1_x.kernel': ((d, shared), 'normal'),
                         b + 'mlp.shared.fc2.kernel': ((shared, d), 'normal')})
        return spec

    spec = {'embed.embedding': ((cfg['vocab_held'], d), 'normal'), 'norm.scale': ((d,), 'ones'),
            'head.kernel': ((d, cfg['vocab_held']), 'normal')}
    for i in range(cfg['num_hidden_layers']):
        spec.update(block(f'blocks.{i}.', i < cfg['first_k_dense_replace']))
    if cfg['num_nextn_predict_layers']:
        spec.update(block('mtp.block.', False))
        spec.update({'mtp.enorm.scale': ((d,), 'ones'), 'mtp.hnorm.scale': ((d,), 'ones'),
                     'mtp.norm.scale': ((d,), 'ones'), 'mtp.eh_proj.kernel': ((2 * d, d), 'normal')})
    return spec


def no_weight_decay(name: str) -> bool:
    """AdamW decays every matrix, the embedding and the expert stacks among them; norm scales are vectors."""
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


def attention(cfg, p, b, x, precision, block_q):
    """Multi-head latent attention of x (S, d), causal."""
    S = x.shape[0]
    H, nope, rd, vd = cfg['num_attention_heads'], cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim'], cfg['v_head_dim']
    eps, rank = cfg['rms_norm_eps'], cfg['kv_lora_rank']
    c_q = rms_norm(ops.matmul(x, p[b + 'attn.q_a.kernel'], precision), p[b + 'attn.q_norm.scale'], eps)
    q = ops.matmul(c_q, p[b + 'attn.q_b.kernel'], precision).reshape(S, H, nope + rd).transpose(1, 0, 2)
    kv = ops.matmul(x, p[b + 'attn.kv_a.kernel'], precision)
    c_kv = rms_norm(kv[:, :rank], p[b + 'attn.kv_norm.scale'], eps)
    k_rope = rope(kv[:, rank:], cfg['rope_theta'])                                        # (S, rd), one for all heads
    kv = ops.matmul(c_kv, p[b + 'attn.kv_b.kernel'], precision).reshape(S, H, nope + vd).transpose(1, 0, 2)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], cfg['rope_theta'])], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope[None], (H, S, rd))], axis=-1)
    v = kv[..., nope:]
    scale = (nope + rd) ** -0.5

    @jax.checkpoint
    def block(qb, kb, vb, first):
        s = ops.einsum('hqd,hkd->hqk', qb, kb, precision) * scale
        seen = jnp.arange(kb.shape[1])[None, :] <= (first + jnp.arange(qb.shape[1]))[:, None]
        return ops.einsum('hqk,hkd->hqd', jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1), vb, precision)

    bq = min(block_q, S)
    out = jnp.concatenate([block(q[:, i:i + bq], k[:, :i + bq], v[:, :i + bq], i) for i in range(0, S, bq)], axis=1)
    return ops.matmul(out.transpose(1, 0, 2).reshape(S, H * vd), p[b + 'attn.o.kernel'], precision)


def routes(cfg, p, b, x, bias=None):
    """Chosen experts (S, k) of all `n_routed_experts` and their weights; float32 at full precision whatever
    the matmuls' `precision` (the configuration states the router in float32)."""
    s = jax.nn.sigmoid(ops.matmul(x, p[b + 'mlp.router'], 'float32'))
    _, idx = jax.lax.top_k(s if bias is None else s + bias, cfg['num_experts_per_tok'])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx, chosen / (chosen.sum(-1, keepdims=True) + 1e-20) * cfg['routed_scaling_factor']


def experts(cfg, p, b, x, precision, bias=None):
    """sum over the held experts of weight x expert(x), plus the shared expert; -> (y, chosen ids)."""
    idx, w = routes(cfg, p, b, x, bias)
    y = swiglu(x, p[b + 'mlp.shared.fc1_g.kernel'], p[b + 'mlp.shared.fc1_x.kernel'], p[b + 'mlp.shared.fc2.kernel'],
               precision) if cfg['n_shared_experts'] else jnp.zeros_like(x)
    for e in range(cfg['experts_held']):
        w_e = jnp.where(idx == cfg['expert_offset'] + e, w, 0.0).sum(-1)                  # 0 where e was not chosen
        y = y + w_e[:, None] * swiglu(x, p[b + 'mlp.w_gate'][e], p[b + 'mlp.w_up'][e], p[b + 'mlp.w_down'][e], precision)
    return y, idx


def layer(cfg, p, b, x, dense, precision, block_q, bias=None):
    x = x + attention(cfg, p, b, rms_norm(x, p[b + 'norm1.scale'], cfg['rms_norm_eps']), precision, block_q)
    h = rms_norm(x, p[b + 'norm2.scale'], cfg['rms_norm_eps'])
    if dense:
        return x + swiglu(h, p[b + 'mlp.fc1_g.kernel'], p[b + 'mlp.fc1_x.kernel'], p[b + 'mlp.fc2.kernel'], precision), None
    y, idx = experts(cfg, p, b, h, precision, bias)
    return x + y, idx


def cross_entropy_sum(cfg, p, norm_scale, h, target, precision):
    """Summed next-token cross-entropy over the positions whose target is not IGNORE, and the logits."""
    logits = ops.matmul(rms_norm(h, norm_scale, cfg['rms_norm_eps']), p['head.kernel'], precision)
    valid = target != IGNORE
    safe = jnp.where(valid, target, 0)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
    return jnp.where(valid, nll, 0.0).sum(), logits


def forward(cfg, p, ids, target, precision: str = 'float32', block_q: int = 1024, biases=None):
    """One sequence: ids, target (S,). -> dict of `loss_main_sum`, `loss_mtp_sum` (summed over their valid
    positions), `n_main`, `n_mtp`, `logits`, `logits_mtp`, `routes` ((expert layers + MTP, S, k) chosen ids).
    `biases` maps a layer's prefix to its `e_score_correction_bias` (default: zero)."""
    biases = biases or {}
    run = jax.checkpoint(lambda p, x, b, dense: layer(cfg, p, b, x, dense, precision, block_q, biases.get(b)),
                         static_argnums=(2, 3))
    x = p['embed.embedding'][ids]
    chosen = []
    for i in range(cfg['num_hidden_layers']):
        x, idx = run(p, x, f'blocks.{i}.', i < cfg['first_k_dense_replace'])
        if idx is not None:
            chosen.append(idx)
    head = jax.checkpoint(lambda p, scale, h, t: cross_entropy_sum(cfg, p, scale, h, t, precision))
    loss_main, logits = head(p, p['norm.scale'], x, target)
    out = {'loss_main_sum': loss_main, 'n_main': (target != IGNORE).sum(), 'logits': logits}
    if cfg['num_nextn_predict_layers']:
        eps = cfg['rms_norm_eps']
        nxt = jnp.where(target == IGNORE, 0, target)
        target2 = jnp.where(target == IGNORE, IGNORE, jnp.concatenate([target[1:], jnp.full((1,), IGNORE, target.dtype)]))
        e = rms_norm(p['embed.embedding'][nxt], p['mtp.enorm.scale'], eps)
        z = ops.matmul(jnp.concatenate([e, rms_norm(x, p['mtp.hnorm.scale'], eps)], axis=-1), p['mtp.eh_proj.kernel'],
                       precision)
        z, idx = run(p, z, 'mtp.block.', False)
        chosen.append(idx)
        loss_mtp, logits_mtp = head(p, p['mtp.norm.scale'], z, target2)
        out.update(loss_mtp_sum=loss_mtp, n_mtp=(target2 != IGNORE).sum(), logits_mtp=logits_mtp)
    out['routes'] = jnp.stack(chosen) if chosen else jnp.zeros((0, ids.shape[0], cfg['num_experts_per_tok']), jnp.int32)
    return out


def loss(cfg, p, ids, target, n_main, n_mtp, precision: str = 'float32', block_q: int = 1024, biases=None):
    """One sequence's share of the batch's loss: its summed cross-entropies over the BATCH's counts of valid
    positions (`n_main`, `n_mtp`), the MTP term weighted. -> (loss share, chosen ids)."""
    out = forward(cfg, p, ids, target, precision, block_q, biases)
    total = out['loss_main_sum'] / n_main
    if cfg['num_nextn_predict_layers']:
        total = total + cfg['mtp_loss_weight'] * out['loss_mtp_sum'] / n_mtp
    return total, out['routes']
