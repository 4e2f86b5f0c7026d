"""Plain reference of the LFM2 mixture-of-experts decoder LM (Liquid AI
LFM2-24B-A2B / LFM2-8B-A1B, ``model_type`` ``lfm2_moe``): float32
``jax.numpy``, no kernels, dense masks, a Python loop over the experts.
Callers wrap it in ``jax.default_matmul_precision("highest")``.

``h`` is the stream (B, T, hidden); every RMSNorm has ``norm_eps`` and a
learned weight; no product has a bias (``conv_bias`` false).

Embedding: ``h = E[ids]``.

Layer ``l``: ``a = h + Mixer(norm_op(h))``, ``h' = a + FF(norm_ffn(a))``.

  ``layer_types[l]`` ``conv``, the double-gated short convolution on
  ``u = norm_op(h)``: ``[B | C | x] = u W_in`` (hidden -> 3 x hidden, split
  in that order along the last axis); ``g = B * x``; ``c_t = sum_{j=0..k-1}
  w[:, j] * g_{t-(k-1)+j}`` with ``k = conv_L_cache`` (depthwise, causal,
  one (hidden, k) weight, zeros before the sequence's start, no bias, no
  activation; written below as the k-term sum it is);
  ``Mixer = (C * c) W_out``.

  ``layer_types[l]`` ``full_attention``: ``q = u W_q`` as (T, heads, d),
  ``k = u W_k``, ``v = u W_v`` as (T, kv heads, d), ``d = hidden / heads``;
  RMSNorm over each head of q and of k (one learned (d,) weight each),
  THEN rotation of all d columns (``rope_parameters.rope_theta``, the
  rotate-half pairing, no scaling); every key ``j <= i`` visible;
  ``o = softmax(q k^T / sqrt(d)) v``, each KV head serving ``heads / kv
  heads`` query heads; ``Mixer = o W_o``.

  ``FF``, layers below ``num_dense_layers``: SwiGLU, ``(silu(m W_1) * (m
  W_3)) W_2`` at ``intermediate_size``.

  ``FF``, the others: ``s = sigmoid(m W_r)`` over ALL ``num_experts``
  outputs; picks = the ``num_experts_per_tok`` largest of ``s + b``
  (``use_expert_bias``: ``b`` enters the choice only); ``w = s[picks] /
  (sum s[picks] + 1e-6)`` (``norm_topk_prob``) times
  ``routed_scaling_factor``; ``sum over the picks that are HELD of w_e *
  (silu(m W_1e) * (m W_3e)) W_2e`` at ``moe_intermediate_size``. No shared
  expert. Where the configuration says ``training.router_gradient``
  ``"none"``, ``s`` is a constant of the backward pass. Where it says
  ``training.router_picks`` ``"token_id"``, the picks of a position are row
  ``t`` of the layer's ``feed_forward.pick_table`` (vocabulary, k), ``t``
  the token at that position, and ``w`` from ``s`` at THOSE picks.

Tail: a final RMSNorm (``embedding_norm``), a head that IS the embedding
matrix (``logits = x E^T`` over the held rows), mean next-token
cross-entropy over them.

Departures from the published description. In WHAT is computed: the
experts are a loop over the HELD ids (``held``, by default the first
``num_experts`` of the file, its count of held experts) with a dense (T,)
weight each, so what the absent experts would add is left out, as in the
program (the cut of ``configs/lfm2-24b-a2b.json``, not a change to a
layer); under that cut with ``training.router_gradient`` ``"none"`` the
router's scores carry no gradient, and with ``training.router_picks``
``"token_id"`` the picks are a fixed table's (a hash layer, Roller et al.,
arXiv:2106.04426), so that a share trained alone keeps a steady load. In
LAYOUT only, so that the program's arrays are read without a copy: q;k;v
are one ``self_attn.qkv_proj.weight`` of stacked rows; the experts are
stacked, ``feed_forward.experts.w1`` / ``w3`` (held, hidden, width) and
``w2`` (held, width, hidden), in the order of ``held``;
``feed_forward.gate.weight`` is (in, out); ``conv.conv.weight`` is (hidden,
k) without the singleton axis. In HOW it is evaluated, never in its value:
attention runs in blocks of ``QUERY_BLOCK`` queries against all keys, each
under ``jax.checkpoint`` as is each expert's weighted term, so that
1 x 8,192 tokens fit on the chip beside the model.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
RENORM_EPS = 1e-6


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def router_width(cfg):
    """The router's outputs: the published expert count where the file
    holds a share."""
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def held_experts(cfg):
    """ids of the routed experts this chip holds: the first ``num_experts``
    (the file's count of HELD experts) of the router's outputs."""
    return tuple(range(cfg["num_experts"]))


def swiglu(u, w1, w3, w2):
    return (jax.nn.silu(u @ w1) * (u @ w3)) @ w2


# ------------------------------------------------------- the short convolution

def short_conv_local(p, pre, bcx, cfg):
    """The mixer between its two products, from ``[B | C | x]``: the gate
    ``B * x``, the k-term causal sum, the gate ``C * c``."""
    e, k = cfg["hidden_size"], cfg["conv_L_cache"]
    b, c, x = bcx[..., :e], bcx[..., e:2 * e], bcx[..., 2 * e:]
    g = b * x
    w = p[pre + "conv.weight"]                              # (hidden, k)
    length = g.shape[1]
    conv = jnp.zeros_like(g)
    for j in range(k):
        back = k - 1 - j                # tap j reads the position t - back
        shifted = jnp.concatenate(
            [jnp.zeros_like(g[:, :back]), g[:, :length - back]], axis=1)
        conv = conv + w[:, j] * shifted
    return c * conv


def short_conv(p, pre, u, cfg):
    """The double-gated short convolution on the normed stream ``u``."""
    return short_conv_local(p, pre, u @ p[pre + "in_proj.weight"].T, cfg) \
        @ p[pre + "out_proj.weight"].T


# -------------------------------------------------------------- attention

def rotate(x, theta):
    """Rotary embedding of (B, T, heads, D) at positions 0..T-1, feature i
    paired with i + D/2."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(x.dtype)


def attention(p, pre, u, cfg):
    """The attention mixer on the normed stream ``u``: ``o W_o``."""
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // h
    eps, theta = cfg["norm_eps"], cfg["rope_parameters"]["rope_theta"]
    bsz, s, _ = u.shape
    qkv = u @ p[pre + "qkv_proj.weight"].T
    q = qkv[..., :h * d].reshape(bsz, s, h, d)
    k = qkv[..., h * d:(h + kv) * d].reshape(bsz, s, kv, d)
    v = qkv[..., (h + kv) * d:].reshape(bsz, s, kv, d)
    q = rotate(rms_norm(q, p[pre + "q_layernorm.weight"], eps), theta)
    k = rotate(rms_norm(k, p[pre + "k_layernorm.weight"], eps), theta)
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
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
    return ctx @ p[pre + "out_proj.weight"].T


# ---------------------------------------------------------------- experts

def route(p, pre, m, cfg, ids0=None):
    """(picked (..., k) ids over ALL the router's outputs, their weights),
    from the normed stream ``m``; ``ids0`` the 0-based tokens of its
    positions."""
    training = cfg.get("training", {})
    s = jax.nn.sigmoid((m @ p[pre + "gate.weight"]).astype(jnp.float32))
    if training.get("router_gradient", "full") == "none":
        s = jax.lax.stop_gradient(s)
    if training.get("router_picks", "scores") == "token_id":
        picked = p[pre + "pick_table"][ids0].astype(jnp.int32)
    else:
        _, picked = jax.lax.top_k(
            s + jax.lax.stop_gradient(p[pre + "expert_bias"]),
            cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(s, picked, -1)
    w = top / (jnp.sum(top, -1, keepdims=True) + RENORM_EPS)
    return picked, (w * cfg.get("routed_scaling_factor", 1.0)).astype(m.dtype)


def experts(p, pre, m, picked, w, held):
    """sum over the held experts e of (weight of e among a token's picks)
    x expert_e(m); the stacked weights lie in the order of ``held``."""

    @jax.checkpoint         # an expert's (T, width) tensors: again backward
    def weighted(mine, m, w1, w3, w2):
        return mine * swiglu(m, w1, w3, w2)

    out = jnp.zeros_like(m)
    for j, eid in enumerate(held):
        mine = jnp.sum(jnp.where(picked == eid, w, 0), -1, keepdims=True)
        out = out + weighted(mine, m, p[pre + "experts.w1"][j],
                             p[pre + "experts.w3"][j],
                             p[pre + "experts.w2"][j])
    return out


# ------------------------------------------------------------------ the model

def layer(p, i, h, cfg, ids0=None, held=None):
    """One layer: (its output, its router's picks or None)."""
    pre, eps = f"model.layers.{i}.", cfg["norm_eps"]
    u = rms_norm(h, p[pre + "operator_norm.weight"], eps)
    if cfg["layer_types"][i] == "conv":
        a = h + short_conv(p, pre + "conv.", u, cfg)
    else:
        a = h + attention(p, pre + "self_attn.", u, cfg)
    m = rms_norm(a, p[pre + "ffn_norm.weight"], eps)
    pre += "feed_forward."
    if i < cfg["num_dense_layers"]:
        return a + swiglu(m, p[pre + "w1.weight"].T, p[pre + "w3.weight"].T,
                          p[pre + "w2.weight"].T), None
    picked, w = route(p, pre, m, cfg, ids0)
    return a + experts(p, pre, m, picked, w,
                       held_experts(cfg) if held is None else held), picked


def hidden(p, ids0, cfg, dtype=jnp.float32):
    """(final hidden states (B, T, hidden), the routers' picks of each
    expert layer in order). ``dtype``: float32, the reference; a lower one
    gives the reading that a tolerance has to keep out."""
    p = {k: v if k.endswith("pick_table") else v.astype(dtype)
         for k, v in p.items()}
    x = p["model.embed_tokens.weight"][ids0]
    picks = []
    for i in range(cfg["num_hidden_layers"]):
        x, picked = layer(p, i, x, cfg, ids0)
        if picked is not None:
            picks.append(picked)
    return rms_norm(x, p["model.embedding_norm.weight"],
                    cfg["norm_eps"]), picks


def loss(p, ids0, targets0, cfg, dtype=jnp.float32):
    """(mean next-token cross-entropy over the held slice, picks); the head
    is the embedding matrix."""
    x, picks = hidden(p, ids0, cfg, dtype)
    lp = jax.nn.log_softmax(
        x @ p["model.embed_tokens.weight"].astype(dtype).T, -1)
    nll = -jnp.take_along_axis(lp, targets0[..., None], -1)
    return jnp.mean(nll.astype(jnp.float32)), picks


def loss_and_grad_norm(p, ids0, targets0, cfg, dtype=jnp.float32):
    """(loss, global L2 norm of its gradient, picks). A pick table and the
    selection bias take no gradient."""
    fixed = {k: v for k, v in p.items()
             if k.endswith(("pick_table", "expert_bias"))}
    (val, picks), g = jax.value_and_grad(
        lambda q: loss(dict(q, **fixed), ids0, targets0, cfg, dtype),
        has_aux=True)({k: v for k, v in p.items() if k not in fixed})
    sq = sum(jnp.sum(x.astype(jnp.float32) ** 2)
             for x in jax.tree_util.tree_leaves(g))
    return val, jnp.sqrt(sq), picks


def pick_stats(picks, cfg):
    """What the routers did on one batch: picks per held expert (mean and
    max over experts and layers) and the share of all picks that went to
    experts held elsewhere."""
    held = jnp.asarray(held_experts(cfg))
    per = jnp.stack([jnp.sum(pk[..., None] == held, axis=tuple(
        range(pk.ndim))) for pk in picks])                  # (layers, held)
    total = sum(pk.size for pk in picks)
    return {"picks_per_held_expert_mean": float(jnp.mean(per)),
            "picks_per_held_expert_max": int(jnp.max(per)),
            "absent_pick_share": float(1.0 - jnp.sum(per) / total)}
