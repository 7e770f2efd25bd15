"""Plain reference of Solar-Open2-250B (`solar_open2`; config.json of upstage/Solar-Open2-250B),
in `jax.numpy` float32 at `Precision.HIGHEST`: a token embedding, pre-norm layers
whose sequence mixer is a softmax attention without positions and with an output
gate (`gqa_layers`) or a gated delta-rule linear attention with a per-channel decay
(KDA, Kimi Linear arXiv:2510.26692; every other layer), every feed-forward
sigmoid-routed sparse SwiGLU experts with a selection bias plus one shared expert,
a final RMSNorm and an untied head. The loss is next-token cross-entropy.

Layer l of h (S, d), every linear map without bias, eps = `rms_norm_eps`:
  a = RMSNorm_1(h)
  attention:  q, k, v = a W_q, a W_k, a W_v (64 query heads of 128 on 8); no positions, no q/k norm;
              h' = h + [softmax(q k^T / sqrt 128 + causal mask) v * sigmoid(a W_gate)] W_o
  KDA:        q~, k~, v = SiLU(conv4(a W_q)), SiLU(conv4(a W_k)), SiLU(conv4(a W_v)), the LAST of 4 taps on the current
              position; q = q~ / ||q~|| / sqrt 128, k = k~ / ||k~|| a head; g = -exp(A_log) softplus(a W_fd W_fu + dt_bias)
              a channel; beta = 2 sigmoid(a W_beta) a head;
              S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T, S_0 = 0; o_t = S_t^T q_t,
              POSITION BY POSITION (`delta_rule`: a `lax.scan`; the program computes chunks, so the two share no algebra);
              h' = h + [RMSNorm_head(o) * sigmoid(a W_gd W_gu)] W_o
  e = RMSNorm_2(h');  s = sigmoid(e W_r); idx = top-8(s + bias); p = s[idx] / (sum s[idx] + 1e-20) * scaling;
  h_out = h' + sum over chosen experts HELD HERE of p_e (silu(e W_1e) * (e W_3e)) W_2e + shared(e)
  logits = RMSNorm_f(h) W_head.

Given ONE CHIP'S SHARE exactly as the program is: `experts_held` routed experts
from `expert_offset` (the router scores all `n_routed_experts`), `heads_held` query
heads of either mixer from `head_offset` with their key/value heads (what the
absent heads would add to the output product is left out), `vocab_held` rows.

What `config.json` does not settle is listed under `assumed` in the
configuration's file. It computes in blocks so that a sequence of 8192 fits: every
layer, every block of queries, every block of `SCAN_BLOCK` positions of the
recurrence and every chunk of the head is rematerialised in the backward pass.
That changes no value. Imports nothing of the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import ops

IGNORE = -1
HEAD_CHUNK = 4096
SCAN_BLOCK = 64         # positions of the recurrence between two kept states
TOPK_NORM_EPS = 1e-20
L2_EPS = 1e-6


def layer_kind(cfg, i: int) -> str:
    return 'attention' if i in cfg['gqa_layers'] else 'kda'


def kv_heads_held(cfg) -> int:
    return cfg['heads_held'] * cfg['num_key_value_heads'] // cfg['num_attention_heads']


def init_spec(cfg) -> dict:
    """name -> (shape, kind): matrices (the taps among them) 'normal' (std 0.02), norm scales 'ones' (1 + normal); `A_log`
    and `dt_bias` are drawn by `finish_weights`."""
    d, hd, taps, rank = cfg['hidden_size'], cfg['head_dim'], cfg['short_conv_kernel_size'], cfg['gate_rank']
    wide, kv_wide = cfg['heads_held'] * hd, kv_heads_held(cfg) * hd
    held, hidden = cfg['experts_held'], cfg['moe_intermediate_size']
    shared = hidden * cfg['n_shared_experts']
    spec = {'embed.embedding': ((cfg['vocab_held'], d), 'normal'), 'norm.scale': ((d,), 'ones'),
            'head.kernel': ((d, cfg['vocab_held']), 'normal')}
    for i in range(cfg['num_hidden_layers']):
        b = f'blocks.{i}.'
        spec.update({b + 'norm1.scale': ((d,), 'ones'), b + 'norm2.scale': ((d,), 'ones')})
        if layer_kind(cfg, i) == 'attention':
            spec.update({b + 'attn.q_proj.kernel': ((d, wide), 'normal'), b + 'attn.k_proj.kernel': ((d, kv_wide), 'normal'),
                         b + 'attn.v_proj.kernel': ((d, kv_wide), 'normal'), b + 'attn.gate_proj.kernel': ((d, wide), 'normal'),
                         b + 'attn.proj.kernel': ((wide, d), 'normal')})
        else:
            k = b + 'kda.'
            spec.update({k + 'q_proj.kernel': ((d, wide), 'normal'), k + 'k_proj.kernel': ((d, wide), 'normal'),
                         k + 'v_proj.kernel': ((d, wide), 'normal'), k + 'q_taps': ((wide, taps), 'normal'),
                         k + 'k_taps': ((wide, taps), 'normal'), k + 'v_taps': ((wide, taps), 'normal'),
                         k + 'f_down.kernel': ((d, rank), 'normal'), k + 'f_up.kernel': ((rank, wide), 'normal'),
                         k + 'beta_proj.kernel': ((d, cfg['heads_held']), 'normal'), k + 'A_log': ((cfg['heads_held'],), 0.0),
                         k + 'dt_bias': ((wide,), 0.0), k + 'g_down.kernel': ((d, rank), 'normal'),
                         k + 'g_up.kernel': ((rank, wide), 'normal'), k + 'o_norm.scale': ((hd,), 'ones'),
                         k + 'o_proj.kernel': ((wide, d), 'normal')})
        spec.update({b + 'mlp.router': ((d, cfg['n_routed_experts']), 'normal'),
                     b + 'mlp.w_gate': ((held, d, hidden), 'normal'), b + 'mlp.w_up': ((held, d, hidden), 'normal'),
                     b + 'mlp.w_down': ((held, hidden, d), 'normal'),
                     b + 'mlp.shared.fc1_g.kernel': ((d, shared), 'normal'), b + 'mlp.shared.fc1_x.kernel': ((d, shared), 'normal'),
                     b + 'mlp.shared.fc2.kernel': ((shared, d), 'normal')})
    return spec


def finish_weights(seed: int, cfg, params: dict) -> dict:
    """The two leaves a normal draw does not fit, from the seed (Kimi Linear's initialisers): `A_log` = log U(1, 16) a
    head, `dt_bias` the inverse softplus of a step log-uniform in (1e-3, 0.1) a channel."""
    key, out = jax.random.key(seed % (2 ** 31)), dict(params)
    for name in sorted(n for n in params if n.endswith('.kda.A_log')):
        k_a, k_dt = jax.random.split(jax.random.fold_in(key, int(name.split('.')[1])))
        dt = jnp.exp(jax.random.uniform(k_dt, params[name[:-len('A_log')] + 'dt_bias'].shape, jnp.float32, math.log(1e-3), math.log(0.1)))
        out[name] = jnp.log(jax.random.uniform(k_a, params[name].shape, jnp.float32, 1.0, 16.0))
        out[name[:-len('A_log')] + 'dt_bias'] = dt + jnp.log(-jnp.expm1(-dt))
    return out


def no_weight_decay(name: str) -> bool:
    """AdamW decays every matrix (the embedding, the expert stacks and the taps among them) by its rank; norm scales,
    `A_log` and `dt_bias` are vectors."""
    return False


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def swiglu(x, gate, up, down, precision):
    return ops.matmul(jax.nn.silu(ops.matmul(x, gate, precision)) * ops.matmul(x, up, precision), down, precision)


def conv_silu(x, w, precision):
    """SiLU of the causal depthwise convolution of x (S, ch) with w (ch, K): tap j meets x_{t - (K - 1 - j)}."""
    S, K = x.shape[0], w.shape[1]
    x, w = ops._operand(x, precision), ops._operand(w, precision)       # a convolution's operands
    back = jnp.pad(x, ((K - 1, 0), (0, 0)))
    return jax.nn.silu(sum(w[:, j] * back[j:j + S] for j in range(K)))


def delta_rule(q, k, v, g, beta, precision: str = 'float32', block: int = SCAN_BLOCK):
    """The recurrence, one position at a time: q, k, g (S, H, D), v (S, H, Dv), beta (S, H) -> o (S, H, Dv). The state
    (H, D, Dv) starts at zero; a block of `block` positions is rematerialised in the backward pass, so the states kept
    are one a block."""
    S, H, D = q.shape

    def one(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[:, :, None] * state
        u = b_t[:, None] * (v_t - ops.einsum('hd,hde->he', k_t, state, precision))
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, ops.einsum('hd,hde->he', q_t, state, precision)

    @jax.checkpoint
    def some(state, xs):
        return jax.lax.scan(one, state, xs)

    block = math.gcd(block, S)
    xs = tuple(t.reshape((S // block, block) + t.shape[1:]) for t in (q, k, v, g, beta))
    _, o = jax.lax.scan(some, jnp.zeros((H, D, v.shape[-1]), jnp.float32), xs)
    return o.reshape((S,) + o.shape[2:])


def kda(cfg, p, b, a, precision):
    """The gated delta-rule mixer of the normalised input a (S, d), for the heads held."""
    S, H, D = a.shape[0], cfg['heads_held'], cfg['head_dim']
    k_ = b + 'kda.'
    lin = lambda x, name: ops.matmul(x, p[k_ + name + '.kernel'], precision)  # noqa: E731
    q, k, v = (conv_silu(lin(a, n + '_proj'), p[k_ + n + '_taps'], precision).reshape(S, H, D) for n in 'qkv')
    q = q * jax.lax.rsqrt(jnp.square(q).sum(-1, keepdims=True) + L2_EPS) * D ** -0.5
    k = k * jax.lax.rsqrt(jnp.square(k).sum(-1, keepdims=True) + L2_EPS)
    f = lin(lin(a, 'f_down'), 'f_up') + p[k_ + 'dt_bias']
    g = -jnp.exp(p[k_ + 'A_log'])[None, :, None] * jax.nn.softplus(f).reshape(S, H, D)
    beta = 2.0 * jax.nn.sigmoid(lin(a, 'beta_proj'))
    o = rms_norm(delta_rule(q, k, v, g, beta, precision), p[k_ + 'o_norm.scale'], cfg['rms_norm_eps'])
    gate = jax.nn.sigmoid(lin(lin(a, 'g_down'), 'g_up'))
    return lin(o.reshape(S, H * D) * gate, 'o_proj')


def attention(cfg, p, b, a, precision, block_q):
    """Gated grouped-query attention of the normalised input a (S, d), causal, no positions, for the heads held, in
    query blocks of `block_q` that are the iterations of one `lax.map` (each reads all S keys, masked)."""
    S, H, KV, D = a.shape[0], cfg['heads_held'], kv_heads_held(cfg), cfg['head_dim']
    heads = lambda t, n: t.reshape(S, n, D).transpose(1, 0, 2)  # noqa: E731
    q = heads(ops.matmul(a, p[b + 'attn.q_proj.kernel'], precision), H).reshape(KV, H // KV, S, D)
    k = heads(ops.matmul(a, p[b + 'attn.k_proj.kernel'], precision), KV)
    v = heads(ops.matmul(a, p[b + 'attn.v_proj.kernel'], precision), KV)
    scale, bq = D ** -0.5, min(block_q, S)

    @jax.checkpoint
    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, axis=2)
        s = ops.einsum('hgqd,hkd->hgqk', qb, k, precision) * scale
        seen = jnp.arange(S)[None, :] <= (i * bq + jnp.arange(bq))[:, None]
        return ops.einsum('hgqk,hkd->hgqd', jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1), v, precision)

    out = jax.lax.map(block, jnp.arange(S // bq))                    # (blocks, KV, G, bq, D)
    out = out.transpose(1, 2, 0, 3, 4).reshape(H, S, D).transpose(1, 0, 2).reshape(S, H * D)
    gate = jax.nn.sigmoid(ops.matmul(a, p[b + 'attn.gate_proj.kernel'], precision))
    return ops.matmul(out * gate, p[b + 'attn.proj.kernel'], precision)


def routes(cfg, p, b, e):
    """Chosen experts (S, k) of all `n_routed_experts` and their weights: the choice by sigmoid score + bias, the weights
    the chosen SCORES over their sum + 1e-20, scaled; float32 at full precision whatever the matmuls' `precision`."""
    s = jax.nn.sigmoid(ops.matmul(e, p[b + 'mlp.router'], 'float32'))
    bias = jnp.asarray(cfg['expert_bias'], jnp.float32) if cfg.get('expert_bias') else 0.0
    _, idx = jax.lax.top_k(s + bias, cfg['num_experts_per_tok'])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx, chosen / (chosen.sum(-1, keepdims=True) + TOPK_NORM_EPS) * cfg['routed_scaling_factor']


def experts(cfg, p, b, e, precision):
    """sum over the held experts of weight x SwiGLU expert(e), plus the shared expert; -> (y, chosen ids). Every held
    expert reads every token and a token's weight for an expert it did not choose is 0; the sum over experts is the
    contraction of ONE product over (expert, hidden): three products a layer."""
    idx, w = routes(cfg, p, b, e)
    held = cfg['expert_offset'] + jnp.arange(cfg['experts_held'])
    w_held = jnp.where(idx[:, :, None] == held, w[:, :, None], 0.0).sum(1)                 # (S, held); 0 where not chosen
    gate = ops.einsum('sd,edh->seh', e, p[b + 'mlp.w_gate'], precision)
    up = ops.einsum('sd,edh->seh', e, p[b + 'mlp.w_up'], precision)
    y = ops.einsum('seh,ehd->sd', jax.nn.silu(gate) * up * w_held[:, :, None], p[b + 'mlp.w_down'], precision)
    if cfg['n_shared_experts']:
        y = y + swiglu(e, p[b + 'mlp.shared.fc1_g.kernel'], p[b + 'mlp.shared.fc1_x.kernel'], p[b + 'mlp.shared.fc2.kernel'], precision)
    return y, idx


def layer(cfg, p, i, x, precision, block_q):
    """-> (x, chosen ids)."""
    b, eps = f'blocks.{i}.', cfg['rms_norm_eps']
    a = rms_norm(x, p[b + 'norm1.scale'], eps)
    x = x + (attention(cfg, p, b, a, precision, block_q) if layer_kind(cfg, i) == 'attention' else kda(cfg, p, b, a, precision))
    y, idx = experts(cfg, p, b, rms_norm(x, p[b + 'norm2.scale'], eps), precision)
    return x + y, idx


def cross_entropy_sum(cfg, p, h, target, precision):
    """Summed next-token cross-entropy over the positions whose target is not IGNORE, and the logits."""
    logits = ops.matmul(rms_norm(h, p['norm.scale'], cfg['rms_norm_eps']), p['head.kernel'], precision)
    valid = target != IGNORE
    safe = jnp.where(valid, target, 0)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
    return jnp.where(valid, nll, 0.0).sum(), logits


def forward(cfg, p, ids, target, precision: str = 'float32', block_q: int = 1024):
    """One sequence: ids, target (S,). -> dict of `loss_main_sum` (summed over its valid positions), `n_main`,
    `logits`, `routes` ((layers, S, k) chosen ids)."""
    cfg = dict(cfg, gqa_layers=tuple(cfg['gqa_layers']))
    run = jax.checkpoint(lambda p, x, i: layer(cfg, p, i, x, precision, block_q), static_argnums=(2,))
    x = p['embed.embedding'][ids]
    chosen = []
    for i in range(cfg['num_hidden_layers']):
        x, idx = run(p, x, i)
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
