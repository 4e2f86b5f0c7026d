"""Plain reference of the Ouro looped decoder LM (ByteDance Ouro-2.6B,
``model_type`` ``ouro``; "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741): float32 ``jax.numpy``, no kernels, no fused
loss, attention as a plain softmax, the loop over the passes WRITTEN OUT.
Callers wrap it in ``jax.default_matmul_precision("highest")``. It shares no
code with ``bigdl_tpu/``.

``h`` is the stream (B, T, hidden); every RMSNorm ``N`` has ``rms_norm_eps``
and its own learned (hidden,) weight; no product has a bias but the gate.

Embedding: ``h_0 = E[ids]``.

Layer ``l``, four norms ("sandwich")::

    a = x + N2(Attn(N1(x)))         y = a + N4(SwiGLU(N3(a)))

  ``Attn``: ``q = u W_q``, ``k = u W_k``, ``v = u W_v``,
  ``num_attention_heads`` heads of ``head_dim`` each (as many key/value
  heads: MHA); q and k rotated at ``rope_theta`` in the half-split
  (``rotate_half``) layout, position ``i`` by angle ``i * theta^(-2j/d)`` on
  the pair ``(j, j + d/2)``; every key ``j <= i`` visible; ``o = softmax(q
  k^T / sqrt(head_dim)) v``; ``Attn = o W_o``.
  ``SwiGLU(u) = (silu(u W_gate) * (u W_up)) W_down``.

The loop, ``P = total_ut_steps`` passes over the SAME weights::

    s_t = Stack(h_{t-1})        all layers in order
    h_t = Nf(s_t)               the model's ONE final norm closes every pass
                                and the next pass reads the normed stream
    z_t = h_t W_head^T          one untied head for all passes
    g_t = h_t . w_g + b_g       ONE exit gate, shared by the passes

The exit distribution a token: ``lam_t = sigmoid(g_t)``; ``p_1 = lam_1``,
``p_t = lam_t prod_{j<t}(1 - lam_j)`` for ``t < P``, ``p_P = prod_{j<P}(1 -
lam_j)`` (``g_P`` is computed and reads no loss).

The training loss (the paper's first-stage objective), ``l_{i,t}`` the
cross-entropy of ``z_t`` at token ``i`` against its label::

    L = mean_i [ sum_t p_{i,t} l_{i,t} - beta H(p_i) ],
    H(p) = -sum_t p_t log p_t

Eval: the last pass's log-probabilities (``early_exit_threshold`` 1: no
pass is left early).

Departures from the published description. In WHAT is computed, none. In
LAYOUT only, so that the program's arrays are read without a copy: q;k;v of
a layer are one ``self_attn.qkv_proj.weight`` of stacked rows. In HOW it is
evaluated, never in its value: attention runs in blocks of ``QUERY_BLOCK``
queries against all keys, each under ``jax.checkpoint``, so does each whole
layer application (P x layers of them: their boundaries are what is kept),
and the cross-entropies are taken ``ROW_BLOCK`` rows at a time under
``jax.checkpoint`` (the (P x T, vocabulary) logits never stand whole: 3.2
GB in float32 at 4 x 4,096 x 49,152).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
ROW_BLOCK = 2048


def rms_norm(x, weight, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def rotate(x, theta):
    """(B, T, heads, d) rotated by position in the half-split layout."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, pre, u, cfg):
    """``Attn`` on the normed stream ``u``: ``o W_o``."""
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    if cfg["num_key_value_heads"] != h:
        raise ValueError("the reference takes as many key/value heads as "
                         "query heads")
    bsz, s, _ = u.shape
    qkv = u @ p[pre + "qkv_proj.weight"].T
    q, k, v = (qkv[..., i * h * d:(i + 1) * h * d].reshape(bsz, s, h, d)
               for i in range(3))
    q, k = rotate(q, cfg["rope_theta"]), rotate(k, cfg["rope_theta"])
    k_pos = jnp.arange(s)[None, :]
    qb = min(QUERY_BLOCK, s)
    if s % qb:
        raise ValueError(f"the reference takes whole blocks of {qb} queries")

    @jax.checkpoint
    def block(args):
        q_blk, q0 = args                              # (B, qb, h, d), ()
        mask = k_pos <= q0 + jnp.arange(qb)[:, None]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) \
            / jnp.sqrt(jnp.asarray(d, q_blk.dtype))
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)

    blocks = q.reshape(bsz, s // qb, qb, h, d).swapaxes(0, 1)
    out = jax.lax.map(block, (blocks, jnp.arange(0, s, qb)))
    ctx = out.swapaxes(0, 1).reshape(bsz, s, h * d)
    return ctx @ p[pre + "o_proj.weight"].T


def swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate.T) * (u @ w_up.T)) @ w_down.T


def layer(p, i, x, cfg):
    """One layer application: both blocks, the four norms."""
    pre, eps = f"model.layers.{i}.", cfg["rms_norm_eps"]
    a = x + rms_norm(
        attention(p, pre + "self_attn.",
                  rms_norm(x, p[pre + "input_layernorm.weight"], eps), cfg),
        p[pre + "input_layernorm_2.weight"], eps)
    ff = swiglu(rms_norm(a, p[pre + "post_attention_layernorm.weight"], eps),
                p[pre + "mlp.gate_proj.weight"],
                p[pre + "mlp.up_proj.weight"],
                p[pre + "mlp.down_proj.weight"])
    return a + rms_norm(ff, p[pre + "post_attention_layernorm_2.weight"],
                        eps)


def pass_streams(p, ids0, cfg, dtype=jnp.float32):
    """The normed streams ``h_1 .. h_P``, a list of (B, T, hidden).
    ``dtype``: float32, the reference; a lower one gives the reading that a
    tolerance has to keep out."""
    p = {k: v.astype(dtype) for k, v in p.items()}
    h = p["model.embed_tokens.weight"][ids0]
    out = []
    for _ in range(cfg["total_ut_steps"]):
        for i in range(cfg["num_hidden_layers"]):
            h = jax.checkpoint(lambda p, x, i=i: layer(p, i, x, cfg))(p, h)
        h = rms_norm(h, p["model.norm.weight"], cfg["rms_norm_eps"])
        out.append(h)
    return out


def exit_distribution(g):
    """``p`` (P, ...) of the gate's logits ``g`` (P, ...), float32."""
    lam = jax.nn.sigmoid(g.astype(jnp.float32))
    left = jnp.ones_like(lam[0])                    # prod_{j<t}(1 - lam_j)
    p = []
    for t in range(lam.shape[0] - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    return jnp.stack(p + [left])


def entropy(p):
    return -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)),
                              0.0), axis=0)


def row_losses(h, w_head, targets0):
    """The cross-entropy of every row of ``h`` (N, hidden) against
    ``targets0`` (N,), float32, ``ROW_BLOCK`` rows of logits at a time."""
    n = h.shape[0]
    rb = min(ROW_BLOCK, n)
    if n % rb:
        raise ValueError(f"the reference takes whole blocks of {rb} rows")

    @jax.checkpoint
    def block(args):
        rows, tgt = args
        lp = jax.nn.log_softmax(rows @ w_head.T, -1)
        return -jnp.take_along_axis(lp, tgt[:, None], -1)[:, 0].astype(
            jnp.float32)

    return jax.lax.map(block, (h.reshape(n // rb, rb, -1),
                               targets0.reshape(n // rb, rb))).reshape(n)


def exit_loss(streams, w_head, g, targets0, beta):
    """``L`` and the (P, B, T) cross-entropies from the P streams (P, B, T,
    hidden), the head, the gate's logits (P, B, T) and the labels."""
    ce = row_losses(streams.reshape(-1, streams.shape[-1]), w_head,
                    jnp.broadcast_to(targets0, g.shape).reshape(-1)
                    ).reshape(g.shape)
    p = exit_distribution(g)
    return jnp.mean(jnp.sum(p * ce, axis=0) - beta * entropy(p)), ce


def gate_logits(p, streams):
    w = p["model.early_exit_gate.weight"].astype(streams.dtype)
    b = p["model.early_exit_gate.bias"].astype(streams.dtype)
    return (streams @ w.T)[..., 0] + b[0]


def loss(p, ids0, targets0, cfg, beta, dtype=jnp.float32):
    streams = jnp.stack(pass_streams(p, ids0, cfg, dtype))
    return exit_loss(streams, p["lm_head.weight"].astype(dtype),
                     gate_logits(p, streams), targets0, beta)[0]


def log_probs(p, ids0, cfg, dtype=jnp.float32):
    """Eval: the last pass's log-probabilities (B, T, vocabulary)."""
    h = pass_streams(p, ids0, cfg, dtype)[-1]
    return jax.nn.log_softmax(h @ p["lm_head.weight"].astype(dtype).T, -1)


def loss_and_grad(p, ids0, targets0, cfg, beta, dtype=jnp.float32):
    return jax.value_and_grad(
        lambda q: loss(q, ids0, targets0, cfg, beta, dtype))(p)


def loss_and_grad_norm(p, ids0, targets0, cfg, beta, dtype=jnp.float32):
    """(loss, global L2 norm of its gradient)."""
    val, g = loss_and_grad(p, ids0, targets0, cfg, beta, dtype)
    sq = sum(jnp.sum(x.astype(jnp.float32) ** 2)
             for x in jax.tree_util.tree_leaves(g))
    return val, jnp.sqrt(sq)
