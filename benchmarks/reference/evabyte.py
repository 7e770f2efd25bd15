"""Plain reference of EvaByte 6.5B (config.json of EvaByte/EvaByte, `model_type`
`evabyte`, `attention_class` `eva`; the attention is EVA, arXiv:2302.04542, in the
deterministic form the model's public modelling code uses), in `jax.numpy`
float32 at `Precision.HIGHEST`: byte embedding, pre-norm layers of a chunk-pooled
linear attention and a dense SwiGLU, a final RMSNorm, an untied head of 8
predictions a position. Nothing to do with the image model EVA-02.

A sequence x of N ids, N a multiple of W = `window_size`; c = `chunk_size`; d =
`head_dim`; every linear map without bias; h float32 throughout.
  RMSNorm(x) = x / sqrt(mean(x^2) + eps) * (1 + g)                      (`norm_add_unit_offset`)
  a = RMSNorm_1(h); q, k, v = a W_q, a W_k, a W_v; q and k turn by the rotary table BEFORE anything below
  summaries, a head with learned phi, mu: for chunk j, alpha_t = softmax over its c positions of k_t . phi;
      k~_j = sum_t alpha_t k_t + mu;  v~_j = sum_t alpha_t v_t
  core: query i sees the single keys {t : t // W == i // W, t <= i} and the summaries {j : j < (W / c) (i // W)}
      (every chunk of every EARLIER window, none of its own), ONE softmax of d^-0.5 q_i . key over both kinds
  h' = h + o W_o;  h_out = h' + (silu(e W_gate) * (e W_up)) W_down,  e = RMSNorm_2(h')
  z_i = RMSNorm_f(h_i) W_head in R^(P x V), head-major; head p predicts x_{i + 1 + p};
  loss = (1 / P) sum_p mean over {i : i + 1 + p inside the window} of nll(z_{i,p}, x_{i + 1 + p})

Given ONE CHIP'S SHARE exactly as the program is: `heads_held` of the
`num_attention_heads` heads from `head_offset` (their columns of W_q, W_k, W_v,
their rows of W_o, their phi and mu), so the attention block gives those heads'
part of the output product and what the other heads would add is left out; the
feed-forward block, the norms, embedding and head are whole.

What `config.json` does not settle, and what is taken here (the configuration
file lists each under `assumed`):
  * no further scale inside the chunk softmax (k_t . phi as it stands);
  * rotary dimensions pair as halves (j with j + d / 2), all d dimensions turn;
  * the 8 heads' losses weigh equally, each a mean over its own valid positions, and the head's columns
    are head-major (head p's are [p V, (p + 1) V));
  * a query sees NO summary of its own window (its own window is exact).

It writes the mathematics the slow way: the summaries by a reshape to (chunks, c)
and a softmax; the core one query WINDOW at a time (`lax.map`), each window's
scores against its own W keys under a lower-triangular mask, concatenated with
its scores against all N / c summaries under j < (W / c) w, one softmax over the
W + N / c columns. Every layer, every window of the core, every row block of the
feed-forward block and every chunk of the head is rematerialised in the backward
pass, so that 16384 positions of hidden 4096 fit beside the weights; that changes
no value. Maps, not Python loops over layers x windows: the TPU compiler's time
(PERF.md section 7 (m)). Imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ops

IGNORE = -1
HEAD_CHUNK = 4096
FFN_ROWS = 2048


def init_spec(cfg) -> dict:
    """name -> (shape, kind): every leaf 'normal' (std 0.02): matrices, the two learned vectors a head, and
    the norms' g (the scale is 1 + g)."""
    d, hd, held, ffn = cfg['hidden_size'], cfg['head_dim'], cfg['heads_held'], cfg['intermediate_size']
    out = cfg['num_pred_heads'] * cfg['vocab_size']
    spec = {'embed.embedding': ((cfg['vocab_size'], d), 'normal'), 'norm.scale': ((d,), 'normal'),
            'head.kernel': ((d, out), 'normal')}
    for i in range(cfg['num_hidden_layers']):
        b = f'blocks.{i}.'
        spec.update({
            b + 'norm1.scale': ((d,), 'normal'), b + 'norm2.scale': ((d,), 'normal'),
            b + 'attn.q_proj.kernel': ((d, held * hd), 'normal'), b + 'attn.k_proj.kernel': ((d, held * hd), 'normal'),
            b + 'attn.v_proj.kernel': ((d, held * hd), 'normal'), b + 'attn.proj.kernel': ((held * hd, d), 'normal'),
            b + 'attn.phi': ((held, hd), 'normal'), b + 'attn.mu': ((held, hd), 'normal'),
            b + 'mlp.fc1_g.kernel': ((d, ffn), 'normal'), b + 'mlp.fc1_x.kernel': ((d, ffn), 'normal'),
            b + 'mlp.fc2.kernel': ((ffn, d), 'normal')})
    return spec


def no_weight_decay(name: str) -> bool:
    """AdamW decays every matrix, the embedding among them; not the two learned vectors a head (the norms' g are
    vectors, which `lm_train_step.follow` leaves out by their rank)."""
    return name.endswith(('.attn.phi', '.attn.mu'))


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + g)


def rope(x, theta):
    """Rotary turn of (..., S, D): dimension j pairs with j + D/2, frequency theta^(-2j/D), position = index."""
    S, D = x.shape[-2], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def summaries(cfg, k, v, phi, mu, precision):
    """k, v (H, N, D) -> k~, v~ (H, N / c, D): a softmax over each chunk's positions of k . phi weighs them."""
    H, N, D = k.shape
    c = cfg['chunk_size']
    kc, vc = k.reshape(H, N // c, c, D), v.reshape(H, N // c, c, D)
    alpha = jax.nn.softmax(ops.einsum('hjtd,hd->hjt', kc, phi, precision), axis=-1)
    return ops.einsum('hjt,hjtd->hjd', alpha, kc, precision) + mu[:, None, :], ops.einsum('hjt,hjtd->hjd', alpha, vc, precision)


def core(cfg, q, k, v, ks, vs, precision):
    """q, k, v (H, N, D), summaries ks, vs (H, N / c, D) -> (H, N, D): one query window at a time."""
    H, N, D = q.shape
    W, c = cfg['window_size'], cfg['chunk_size']
    scale, per_window = D ** -0.5, W // c
    local = jnp.tril(jnp.ones((W, W), bool))
    chunk_index = jnp.arange(N // c)[None, :]

    @jax.checkpoint
    def window(w):
        qw, kw, vw = (jax.lax.dynamic_slice_in_dim(t, w * W, W, axis=1) for t in (q, k, v))
        own = jnp.where(local, ops.einsum('hqd,hkd->hqk', qw, kw, precision) * scale, -jnp.inf)
        earlier = jnp.where(chunk_index < per_window * w, ops.einsum('hqd,hjd->hqj', qw, ks, precision) * scale, -jnp.inf)
        p = jax.nn.softmax(jnp.concatenate([own, earlier], axis=-1), axis=-1)
        return ops.einsum('hqk,hkd->hqd', p[..., :W], vw, precision) + ops.einsum('hqj,hjd->hqd', p[..., W:], vs, precision)

    out = jax.lax.map(window, jnp.arange(N // W))                   # (windows, H, W, D)
    return out.transpose(1, 0, 2, 3).reshape(H, N, D)


def attention(cfg, p, b, a, precision):
    """The held heads' part of the attention block's output product for the normalised input a (N, d)."""
    N = a.shape[0]
    H, D = cfg['heads_held'], cfg['head_dim']
    heads = lambda t: t.reshape(N, H, D).transpose(1, 0, 2)  # noqa: E731
    q, k, v = (heads(ops.matmul(a, p[b + f'attn.{name}_proj.kernel'], precision)) for name in 'qkv')
    q, k = rope(q, cfg['rope_theta']), rope(k, cfg['rope_theta'])
    ks, vs = summaries(cfg, k, v, p[b + 'attn.phi'], p[b + 'attn.mu'], precision)
    out = core(cfg, q, k, v, ks, vs, precision)
    return ops.matmul(out.transpose(1, 0, 2).reshape(N, H * D), p[b + 'attn.proj.kernel'], precision)


def feed_forward(p, b, e, precision):
    """SwiGLU of e (N, d), row block by row block (it is row-wise: the blocks change no value)."""
    @jax.checkpoint
    def rows(x):
        gate, up = ops.matmul(x, p[b + 'mlp.fc1_g.kernel'], precision), ops.matmul(x, p[b + 'mlp.fc1_x.kernel'], precision)
        return ops.matmul(jax.nn.silu(gate) * up, p[b + 'mlp.fc2.kernel'], precision)

    N, d = e.shape
    block = min(FFN_ROWS, N)
    return jax.lax.map(rows, e.reshape(N // block, block, d)).reshape(N, d)


def layer(cfg, p, i, x, precision):
    b, eps = f'blocks.{i}.', cfg['rms_norm_eps']
    x = x + attention(cfg, p, b, rms_norm(x, p[b + 'norm1.scale'], eps), precision)
    return x + feed_forward(p, b, rms_norm(x, p[b + 'norm2.scale'], eps), precision)


def head_targets(cfg, target):
    """target (S,), `target[i]` the id after position i -> (S, P): head p's target at i is the id p + 1
    positions on, IGNORE where the window ends before it."""
    return jnp.stack([jnp.pad(target[p:], (0, p), constant_values=IGNORE) for p in range(cfg['num_pred_heads'])], axis=-1)


def cross_entropy_sums(cfg, p, h, targets, precision):
    """Each head's summed cross-entropy over the positions whose target is not IGNORE (P,), and the logits."""
    logits = ops.matmul(rms_norm(h, p['norm.scale'], cfg['rms_norm_eps']), p['head.kernel'], precision)
    by_head = logits.reshape(h.shape[0], cfg['num_pred_heads'], cfg['vocab_size'])
    valid = targets != IGNORE
    safe = jnp.where(valid, targets, 0)
    nll = jax.nn.logsumexp(by_head, axis=-1) - jnp.take_along_axis(by_head, safe[..., None], axis=-1)[..., 0]
    return jnp.where(valid, nll, 0.0).sum(0), logits


def forward(cfg, p, ids, target, precision: str = 'float32', block_q: int = 1024):
    """One sequence: ids, target (S,). -> dict of `loss_heads_sum` (P,) (each head's cross-entropy summed over its
    valid positions), `n_heads` (P,), `logits` (S, P * V). `block_q` is `lm_train_step.py`'s and unused: the core
    goes a window at a time."""
    del block_q
    run = jax.checkpoint(lambda p, x, i: layer(cfg, p, i, x, precision), static_argnums=(2,))
    x = p['embed.embedding'][ids]
    for i in range(cfg['num_hidden_layers']):
        x = run(p, x, i)
    targets = head_targets(cfg, target)
    head = jax.checkpoint(lambda p, h, t: cross_entropy_sums(cfg, p, h, t, precision))
    parts = [head(p, x[i:i + HEAD_CHUNK], targets[i:i + HEAD_CHUNK]) for i in range(0, x.shape[0], HEAD_CHUNK)]
    return {'loss_heads_sum': sum(s for s, _ in parts), 'n_heads': (targets != IGNORE).sum(0),
            'logits': jnp.concatenate([l for _, l in parts], axis=0)}


def loss(cfg, p, ids, target, n_main, n_mtp=None, precision: str = 'float32', block_q: int = 1024):
    """One sequence's share of the batch's loss: the equal-weight mean over the P heads of the head's summed
    cross-entropy over the BATCH's count of its valid positions. `lm_train_step.py` hands in `n_main`, the
    batch's count for head 0; every sequence of this feed has S - 1 - p valid positions for head p (the feed
    check holds the targets to that), so the batch has n_main / (S - 1) sequences and head p's count follows.
    -> (loss share, an empty array where the other families return their chosen experts: no router here)."""
    out = forward(cfg, p, ids, target, precision, block_q)
    S, heads = ids.shape[0], cfg['num_pred_heads']
    counts = n_main // (S - 1) * (S - 1 - jnp.arange(heads))
    return (out['loss_heads_sum'] / counts).mean(), jnp.zeros((0,), jnp.int32)
