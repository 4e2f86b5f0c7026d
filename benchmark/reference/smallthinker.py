"""Plain reference of the SmallThinker decoder LM (PowerInfer
SmallThinker-21BA3B-Instruct / 4BA0.6B, arXiv:2507.20984): float32
``jax.numpy``, no kernels, dense masks, a Python loop over the experts.
Callers wrap it in ``jax.default_matmul_precision("highest")``.

``h`` is the stream (B, T, hidden) that ENTERS a layer; every RMSNorm has
``rms_norm_eps`` and a learned weight; no product has a bias.

Embedding: ``h = E[ids]``.

Layer ``l`` (``rope_layout[l]``, ``sliding_window_layout[l]``):

  router, FIRST, from the layer's input: ``r = h W_r`` over ALL
  ``moe_num_primary_experts`` outputs (the stream itself, not a normed
  copy); picks = the ``moe_num_active_primary_experts`` largest of ``r``;
  ``w = softmax(r[picks])`` over the picked logits
  (``moe_primary_router_apply_softmax`` with ``norm_topk_prob``: the same
  as a softmax over all outputs renormalised over the picks). No selection
  bias, no scale.

  attention: ``u = norm_in(h)``; ``q = u W_q`` as (T, heads, head_dim),
  ``k = u W_k``, ``v = u W_v`` as (T, kv heads, head_dim); where
  ``rope_layout[l]`` is 1 q and k are rotated (``rope_theta``, the
  rotate-half pairing, no scaling); where ``sliding_window_layout[l]`` is 1
  query i sees the keys j with ``i - sliding_window_size < j <= i``, else
  every ``j <= i``; ``o = softmax(q k^T / sqrt(head_dim)) v``, each KV head
  serving ``heads / kv heads`` query heads; ``a = h + o W_o``.

  experts: ``m = norm_post_attn(a)``; ``h' = a + sum over the picks that
  are HELD of w_e * (relu(m W_gate_e) * (m W_up_e)) W_down_e`` (ReGLU). No
  shared expert. Where the configuration says
  ``training.router_gradient`` ``"none"``, ``r`` is a constant of the
  backward pass. Where it says ``training.router_picks`` ``"token_id"``,
  the picks of a position are row ``t`` of the layer's
  ``block_sparse_moe.primary_router.pick_table`` (vocabulary, k), ``t``
  the token at that position, and ``w`` the softmax over ``r`` at THOSE
  picks.

Tail: final RMSNorm, an UNTIED head over the held rows of the vocabulary,
mean next-token cross-entropy over them.

Departures from the published description. In WHAT is computed, one: the
experts are a loop over the HELD ids (``held``, by default the first
``moe_num_primary_experts`` of the file, its count of held experts) with a
dense (T,) weight each, so what the absent experts would add is left out,
as in the program (the cut of ``configs/smallthinker-21b-a3b.json``, not a
change to a layer); and under that cut with ``training.router_gradient``
``"none"`` the router's logits carry no gradient (the sum over the HELD
picks gives only this chip's part of it); and with
``training.router_picks`` ``"token_id"`` the picks are a fixed table's (a
hash layer, Roller et al., arXiv:2106.04426, filled at set-up with the
seeded routers' own picks over each token's embedding row), so that a
share trained alone keeps a steady load. In LAYOUT only, so that the
program's arrays are read without a copy: q;k;v are one
``self_attn.qkv_proj.weight`` of stacked rows; the experts are stacked,
``block_sparse_moe.experts.gate_proj`` / ``up_proj`` (held, hidden, width)
and ``down_proj`` (held, width, hidden), in the order of ``held``;
``block_sparse_moe.primary_router.weight`` is (in, out). In HOW it is
evaluated, never in its value: attention runs in blocks of ``QUERY_BLOCK``
queries against all keys (``jax.lax.map`` over the blocks, so that one
block is compiled once), each under ``jax.checkpoint`` as is each expert's
weighted term, so that 1 x 8,192 tokens of the model and 1 x 16,384 of one
attention block fit on the chip (a (heads, T, T) float32 score tensor is
7.5 GB at 8,192 and 28 heads).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def router_width(cfg):
    """The router's outputs: the published expert count where the file
    holds a share."""
    return cfg.get("published", {}).get("moe_num_primary_experts",
                                        cfg["moe_num_primary_experts"])


def held_experts(cfg):
    """ids of the routed experts this chip holds: the first
    ``moe_num_primary_experts`` (the file's count of HELD experts) of the
    router's outputs."""
    return tuple(range(cfg["moe_num_primary_experts"]))


def relu_gated(u, gate, up, down):
    return (jax.nn.relu(u @ gate) * (u @ up)) @ down


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


def attention(p, pre, u, cfg, rope, windowed):
    """The attention mixer on the normed stream ``u``: ``o W_o``."""
    h, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    bsz, s, _ = u.shape
    qkv = u @ p[pre + "qkv_proj.weight"].T
    q = qkv[..., :h * d].reshape(bsz, s, h, d)
    k = qkv[..., h * d:(h + kv) * d].reshape(bsz, s, kv, d)
    v = qkv[..., (h + kv) * d:].reshape(bsz, s, kv, d)
    if rope:
        q, k = rotate(q, cfg["rope_theta"]), rotate(k, cfg["rope_theta"])
    window = cfg["sliding_window_size"] if windowed else None
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    k_pos = jnp.arange(s)[None, :]
    qb = min(QUERY_BLOCK, s)
    if s % qb:
        raise ValueError(f"the reference takes whole blocks of {qb} queries")

    @jax.checkpoint
    def block(args):
        q_blk, q0 = args                              # (B, qb, h, d), ()
        q_pos = q0 + jnp.arange(qb)[:, None]
        mask = k_pos <= q_pos
        if window is not None:
            mask = mask & (k_pos > q_pos - window)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) \
            / jnp.sqrt(jnp.asarray(d, q_blk.dtype))
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)

    blocks = q.reshape(bsz, s // qb, qb, h, d).swapaxes(0, 1)
    out = jax.lax.map(block, (blocks, jnp.arange(0, s, qb)))
    ctx = out.swapaxes(0, 1).reshape(bsz, s, h * d)
    return ctx @ p[pre + "o_proj.weight"].T


# ---------------------------------------------------------------- experts

def route(p, pre, h, cfg, ids0=None):
    """(picked (..., k) ids over ALL the router's outputs, their weights),
    from the stream ``h`` that entered the layer; ``ids0`` the 0-based
    tokens of ``h``'s positions."""
    training = cfg.get("training", {})
    r = (h @ p[pre + "primary_router.weight"]).astype(jnp.float32)
    if training.get("router_gradient", "full") == "none":
        r = jax.lax.stop_gradient(r)
    if training.get("router_picks", "scores") == "token_id":
        picked = p[pre + "primary_router.pick_table"][ids0].astype(jnp.int32)
        top = jnp.take_along_axis(r, picked, -1)
    else:
        top, picked = jax.lax.top_k(r, cfg["moe_num_active_primary_experts"])
    return picked, jax.nn.softmax(top, -1).astype(h.dtype)


def experts(p, pre, m, picked, w, held):
    """sum over the held experts e of (weight of e among a token's picks)
    x expert_e(m); the stacked weights lie in the order of ``held``."""

    @jax.checkpoint         # an expert's (T, width) tensors: again backward
    def weighted(mine, m, gate, up, down):
        return mine * relu_gated(m, gate, up, down)

    out = jnp.zeros_like(m)
    for j, eid in enumerate(held):
        mine = jnp.sum(jnp.where(picked == eid, w, 0), -1, keepdims=True)
        out = out + weighted(mine, m, p[pre + "experts.gate_proj"][j],
                             p[pre + "experts.up_proj"][j],
                             p[pre + "experts.down_proj"][j])
    return out


# ------------------------------------------------------------------ the model

def layer(p, i, h, cfg, ids0=None, held=None):
    """One layer: (its output, its router's picks)."""
    pre, eps = f"model.layers.{i}.", cfg["rms_norm_eps"]
    picked, w = route(p, pre + "block_sparse_moe.", h, cfg, ids0)
    a = h + attention(p, pre + "self_attn.",
                      rms_norm(h, p[pre + "input_layernorm.weight"], eps),
                      cfg, cfg["rope_layout"][i],
                      cfg["sliding_window_layout"][i])
    m = rms_norm(a, p[pre + "post_attention_layernorm.weight"], eps)
    return a + experts(p, pre + "block_sparse_moe.", m, picked, w,
                       held_experts(cfg) if held is None else held), picked


def hidden(p, ids0, cfg, dtype=jnp.float32):
    """(final hidden states (B, T, hidden), the routers' picks of each
    layer in order). ``dtype``: float32, the reference; a lower one gives
    the reading that a tolerance has to keep out."""
    p = {k: v if k.endswith("pick_table") else v.astype(dtype)
         for k, v in p.items()}
    x = p["model.embed_tokens.weight"][ids0]
    picks = []
    for i in range(cfg["num_hidden_layers"]):
        x, picked = layer(p, i, x, cfg, ids0)
        picks.append(picked)
    return rms_norm(x, p["model.norm.weight"], cfg["rms_norm_eps"]), picks


def loss(p, ids0, targets0, cfg, dtype=jnp.float32):
    """(mean next-token cross-entropy over the held slice, picks)."""
    x, picks = hidden(p, ids0, cfg, dtype)
    lp = jax.nn.log_softmax(x @ p["lm_head.weight"].astype(dtype).T, -1)
    nll = -jnp.take_along_axis(lp, targets0[..., None], -1)
    return jnp.mean(nll.astype(jnp.float32)), picks


def loss_and_grad_norm(p, ids0, targets0, cfg, dtype=jnp.float32):
    """(loss, global L2 norm of its gradient, picks). A pick table takes
    no gradient: its rows are expert ids."""
    tables = {k: v for k, v in p.items() if k.endswith("pick_table")}
    (val, picks), g = jax.value_and_grad(
        lambda q: loss(dict(q, **tables), ids0, targets0, cfg, dtype),
        has_aux=True)({k: v for k, v in p.items() if k not in tables})
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
