"""Plain reference of the ``afmoe`` decoder LM (Arcee Trinity,
``AfmoeForCausalLM``): float32 ``jax.numpy``, no kernels, dense masks, a
Python loop over the experts. Callers wrap it in
``jax.default_matmul_precision("highest")``.

``h`` is (B, T, hidden); every RMSNorm has ``rms_norm_eps`` and a learned
weight.

Embedding: ``h = E[ids] * sqrt(hidden)`` (``mup_enabled``).

Attention half of a layer: ``a = norm_in(h)``; ``q = a W_q`` as
  (T, heads, head_dim), ``k = a W_k``, ``v = a W_v`` as (T, kv heads,
  head_dim), ``g = a W_g`` as (T, heads * head_dim); ``q = norm_q(q)``,
  ``k = norm_k(k)`` over each head's ``head_dim``; on a
  ``sliding_attention`` layer q and k are rotated (``rope_theta``, the
  rotate-half pairing, no scaling) and query i sees the keys j with
  ``i - sliding_window < j <= i``; on a ``full_attention`` layer nothing is
  rotated and it sees ``j <= i``; ``o = softmax(q k^T / sqrt(head_dim)) v``,
  each KV head serving ``heads / kv heads`` query heads;
  ``h = h + norm_post_attn((o * sigmoid(g)) W_o)``. No bias anywhere.

Feed-forward half: ``m = norm_pre_mlp(h)``. In the leading
  ``num_dense_layers``: ``f = (silu(m W_gate) * (m W_up)) W_down``. After
  them: ``s = sigmoid(m W_r)`` over ALL the router's outputs; picks = top
  ``num_experts_per_tok`` of ``s + b``; ``w = s[picks] / (sum s[picks] +
  1e-20) * route_scale``; ``f = shared(m) + sum over the picks that are
  HELD of w_e * expert_e(m)``, ``shared`` and each ``expert_e`` the gated
  form above at ``moe_intermediate_size``.
  ``h = h + norm_post_mlp(f)``. Where the configuration says
  ``training.router_gradient`` ``"none"``, ``s`` is a constant of the
  backward pass (below).

Tail: final RMSNorm, an UNTIED head over the held rows of the vocabulary,
mean next-token cross-entropy over them.

Departures from the published description. In WHAT is computed, one: the
experts are a loop over the HELD ids with a dense (T,) weight each, so what
the absent experts would add is left out, as in the program (the cut of
``configs/trinity-mini.json``, not a change to a layer); and under that
cut with ``training.router_gradient`` ``"none"`` the router's scores carry
no gradient, because the sum over the HELD picks gives only this chip's
part of it (a deployment completes it by exchange; applied alone it pulls
the picks onto the held experts). In LAYOUT only, so
that the program's arrays are read without a copy: q;k;v are one
``self_attn.qkv_proj.weight`` of stacked rows; the routed experts are
stacked, ``mlp.experts.gate_proj`` / ``up_proj`` (held, hidden, width) and
``mlp.experts.down_proj`` (held, width, hidden), in the order of
``held_experts``; ``mlp.shared_experts.*`` and ``mlp.router.gate.weight``
are (in, out). In HOW it is evaluated, never in its value: attention runs
in blocks of ``QUERY_BLOCK`` queries against all keys, and each such block
and each expert's weighted term runs under ``jax.checkpoint``, so that
1 x 4096 tokens at the published widths fit beside the model (a (heads, T,
T) float32 score tensor is 2.1 GB a layer, the held experts' outputs
2.1 GB a model). Not each LAYER: that compiles for 200 s on the chip's
host, this for a third of it (PR 30).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def held_experts(cfg):
    """ids of the routed experts this chip holds: the first ``num_experts``
    (the file's count of HELD experts) of the router's outputs."""
    return tuple(range(cfg["num_experts"]))


def silu_gated(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


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


def attention(p, pre, u, cfg, kind):
    h, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    bsz, s, _ = u.shape
    qkv = u @ p[pre + "qkv_proj.weight"].T
    q = qkv[..., :h * d].reshape(bsz, s, h, d)
    k = qkv[..., h * d:(h + kv) * d].reshape(bsz, s, kv, d)
    v = qkv[..., (h + kv) * d:].reshape(bsz, s, kv, d)
    q = rms_norm(q, p[pre + "q_norm.weight"], eps)
    k = rms_norm(k, p[pre + "k_norm.weight"], eps)
    window = None
    if kind == "sliding_attention":
        q, k = rotate(q, cfg["rope_theta"]), rotate(k, cfg["rope_theta"])
        window = cfg["sliding_window"]
    elif kind != "full_attention":
        raise ValueError(f"unknown layer type {kind!r}")
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    k_pos = jnp.arange(s)[None, :]

    @jax.checkpoint
    def block(qb, k, v, q0):
        q_pos = q0 + jnp.arange(qb.shape[1])[:, None]
        mask = k_pos <= q_pos
        if window is not None:
            mask = mask & (k_pos > q_pos - window)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) \
            / jnp.sqrt(jnp.asarray(d, qb.dtype))
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)

    out = [block(q[:, q0:q0 + QUERY_BLOCK], k, v, q0)
           for q0 in range(0, s, QUERY_BLOCK)]
    ctx = jnp.concatenate(out, 1).reshape(bsz, s, h * d)
    gate = jax.nn.sigmoid(u @ p[pre + "gate_proj.weight"].T)
    return (ctx * gate) @ p[pre + "o_proj.weight"].T


# ---------------------------------------------------------- feed-forward

def dense_mlp(p, pre, u):
    return silu_gated(u, p[pre + "gate_proj.weight"].T,
                      p[pre + "up_proj.weight"].T,
                      p[pre + "down_proj.weight"].T)


def route(p, pre, u, cfg):
    """(picked (..., k) ids over ALL experts, their weights)."""
    s = jax.nn.sigmoid((u @ p[pre + "router.gate.weight"])
                       .astype(jnp.float32))
    if cfg.get("training", {}).get("router_gradient", "full") == "none":
        s = jax.lax.stop_gradient(s)
    _, picked = jax.lax.top_k(s + p[pre + "expert_bias"],
                              cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, picked, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return picked, (w * cfg["route_scale"]).astype(u.dtype)


def moe(p, pre, u, cfg):
    picked, w = route(p, pre, u, cfg)
    out = silu_gated(u, p[pre + "shared_experts.gate_proj.weight"],
                     p[pre + "shared_experts.up_proj.weight"],
                     p[pre + "shared_experts.down_proj.weight"])

    @jax.checkpoint         # an expert's (T, width) tensors: again backward
    def weighted(mine, u, gate, up, down):
        return mine * silu_gated(u, gate, up, down)

    for j, eid in enumerate(held_experts(cfg)):
        mine = jnp.sum(jnp.where(picked == eid, w, 0), -1, keepdims=True)
        out = out + weighted(mine, u, p[pre + "experts.gate_proj"][j],
                             p[pre + "experts.up_proj"][j],
                             p[pre + "experts.down_proj"][j])
    return out, picked


# ------------------------------------------------------------------ the model

def layer(p, i, x, cfg):
    """One layer: (its output, its router's picks or None). Also the
    output of each HALF, for the tests that compare one block."""
    pre, eps = f"model.layers.{i}.", cfg["rms_norm_eps"]
    a = attention(p, pre + "self_attn.",
                  rms_norm(x, p[pre + "input_layernorm.weight"], eps), cfg,
                  cfg["layer_types"][i])
    x = x + rms_norm(a, p[pre + "post_attention_layernorm.weight"], eps)
    m = rms_norm(x, p[pre + "pre_mlp_layernorm.weight"], eps)
    if i < cfg["num_dense_layers"]:
        f, picked = dense_mlp(p, pre + "mlp.", m), None
    else:
        f, picked = moe(p, pre + "mlp.", m, cfg)
    return x + rms_norm(f, p[pre + "post_mlp_layernorm.weight"], eps), picked


def embed(p, ids0, cfg):
    x = p["model.embed_tokens.weight"][ids0]
    if cfg.get("mup_enabled", False):
        x = x * jnp.sqrt(jnp.asarray(cfg["hidden_size"], x.dtype))
    return x


def hidden(p, ids0, cfg, dtype=jnp.float32):
    """(final hidden states (B, T, hidden), the routers' picks of each
    expert layer in order). ``dtype``: float32, the reference; a lower one
    gives the reading that a tolerance has to keep out."""
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), p)
    x = embed(p, ids0, cfg)
    picks = []
    for i in range(cfg["num_hidden_layers"]):
        x, picked = layer(p, i, x, cfg)
        if picked is not None:
            picks.append(picked)
    return rms_norm(x, p["model.norm.weight"], cfg["rms_norm_eps"]), picks


def loss(p, ids0, targets0, cfg, dtype=jnp.float32):
    """(mean next-token cross-entropy over the held slice, picks)."""
    x, picks = hidden(p, ids0, cfg, dtype)
    lp = jax.nn.log_softmax(x @ p["lm_head.weight"].astype(dtype).T, -1)
    nll = -jnp.take_along_axis(lp, targets0[..., None], -1)
    return jnp.mean(nll.astype(jnp.float32)), picks


def loss_and_grad_norm(p, ids0, targets0, cfg, dtype=jnp.float32):
    """(loss, global L2 norm of its gradient, picks). The selection bias
    (``expert_bias``) takes no gradient: it is added to the scores inside
    ``top_k`` only, whose picks are integers."""
    (val, picks), g = jax.value_and_grad(loss, has_aux=True)(
        p, ids0, targets0, cfg, dtype)
    sq = sum(jnp.sum(x.astype(jnp.float32) ** 2)
             for x in jax.tree_util.tree_leaves(g))
    return val, jnp.sqrt(sq), picks


def pick_stats(picks, cfg):
    """What the routers did on one batch: picks per held expert (mean and
    max over experts and expert layers) and the share of all picks that
    went to experts held elsewhere."""
    held = jnp.asarray(held_experts(cfg))
    per = jnp.stack([jnp.sum(pk[..., None] == held, axis=tuple(
        range(pk.ndim))) for pk in picks])                  # (layers, held)
    total = sum(pk.size for pk in picks)
    return {"picks_per_held_expert_mean": float(jnp.mean(per)),
            "picks_per_held_expert_max": int(jnp.max(per)),
            "absent_pick_share": float(1.0 - jnp.sum(per) / total)}
