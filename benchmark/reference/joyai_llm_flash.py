"""Plain reference of the ``joyai_llm_flash`` decoder LM (JoyAI-LLM-Flash;
every key and every layer is DeepSeek-V3's): float32 ``jax.numpy``, no
kernels, dense masks, a Python loop over the experts. Callers wrap it in
``jax.default_matmul_precision("highest")``.

``h`` is (B, T, hidden); every RMSNorm has ``rms_norm_eps`` and a learned
weight; no bias anywhere; a layer is ``h += attn(norm_in(h))``,
``h += ffn(norm_post_attn(h))``.

Latent attention, ``a = norm_in(h)``, ``n`` heads, ``dc`` =
  ``qk_nope_head_dim``, ``dr`` = ``qk_rope_head_dim``, ``dv`` =
  ``v_head_dim``: ``cq = norm_q(a W_qa)`` (``q_lora_rank``); ``[qc ; qr] =
  cq W_qb`` as (T, n, dc + dr); ``[ckv ; kr] = a W_kva`` (``kv_lora_rank``
  + dr: ``kr`` is ONE rotary key for all heads); ``[kc ; v] = norm_kv(ckv)
  W_kvb`` as (T, n, dc + dv); ``qr`` and ``kr`` rotated (``rope_theta``,
  the rotate-half pairing, no scaling); ``q = [qc ; qr]``, ``k = [kc ;
  kr]``; query i sees the keys j <= i; ``o = softmax(q k^T / sqrt(dc +
  dr)) v``; the layer adds ``concat(o) W_o``.

Feed-forward, ``m = norm_post_attn(h)``. In the leading
  ``first_k_dense_replace`` layers: ``(silu(m W_gate) * (m W_up)) W_down``
  at ``intermediate_size``. After them: ``s = sigmoid(m W_r)`` over ALL the
  router's outputs; picks = top ``num_experts_per_tok`` of ``s + b``
  (``noaux_tc``; ``n_group`` 1, so no group limit); ``w = s[picks] / (sum
  s[picks] + 1e-20) * routed_scaling_factor``; ``shared(m) + sum over the
  picks that are HELD of w_e * expert_e(m)``, each the gated form above at
  ``moe_intermediate_size``. Where the configuration says
  ``training.router_gradient`` ``"none"``, ``s`` is a constant of the
  backward pass (below). Where it says ``training.router_picks``
  ``"token_id"``, the picks of a position are row ``t`` of the layer's
  ``mlp.gate.pick_table`` (vocabulary, k), ``t`` the token whose embedding
  the position took in, and only ``w`` comes from the live ``s`` (below).

Tail: final RMSNorm, an UNTIED head over the held rows of the vocabulary,
``L_main`` = mean next-token cross-entropy over them.

Multi-token prediction (``num_nextn_predict_layers`` 1), over the stream
  ``h`` of the last main layer BEFORE the final norm: for positions
  i = 0 .. T-2, ``z_i = [norm_e(E[t_{i+1}]) ; norm_h(h_i)] W_eh`` (2 hidden
  -> hidden; the embedding's half first), one more layer of the main kind
  (latent attention, causal over those T-1 positions at rotary positions
  0 .. T-2, and experts), a norm of its own, the SAME head; ``L_mtp`` =
  mean cross-entropy of position i against ``t_{i+2}``, the target of
  position i + 1. The loss is ``L_main + training.mtp_loss_weight *
  L_mtp``. Stored as layer ``num_hidden_layers`` (``enorm``, ``hnorm``,
  ``eh_proj``, ``shared_head.norm`` beside the layer's own keys), as the
  DeepSeek-V3 checkpoint stores it.

Departures from the published description. In WHAT is computed, one: the
experts are a loop over the HELD ids with a dense (T,) weight each, so what
the absent experts would add is left out, as in the program (the cut of
``configs/joyai-llm-flash.json``, not a change to a layer); and under that
cut with ``training.router_gradient`` ``"none"`` the router's scores carry
no gradient, because the sum over the HELD picks gives only this chip's
part of it (a deployment completes it by exchange; applied alone it pulls
the picks onto the held experts); and with ``training.router_picks``
``"token_id"`` the picks are a fixed table's (a hash layer, Roller et al.,
arXiv:2106.04426, filled at set-up from the seeded routers' own scores of
each token's embedding row): a model at its seeded weights trained at the
full rate moves its whole stream a common way within ten steps and every
token then picks the same experts, which no deployment's routers do, and
the work of a held share follows that and not the program. Assumed, not published (the config's
``assumed``): which stream the module reads, the order of the halves of
``W_eh``, the loss weight. In LAYOUT only, so that the program's arrays are
read without a copy: the routed experts are stacked,
``mlp.experts.gate_proj`` / ``up_proj`` (held, hidden, width) and
``mlp.experts.down_proj`` (held, width, hidden), in the order of
``held_experts``; ``mlp.shared_experts.*`` and ``mlp.gate.weight`` are
(in, out); every other matrix is (out, in). In HOW it is evaluated, never
in its value: attention runs in blocks of ``QUERY_BLOCK`` queries against
all keys, and each such block and each expert's weighted term runs under
``jax.checkpoint``, so that the compared batch at the published widths
fits beside the model.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def held_experts(cfg):
    """ids of the routed experts this chip holds: the first
    ``n_routed_experts`` (the file's count of HELD experts) of the router's
    outputs."""
    return tuple(range(cfg["n_routed_experts"]))


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


def attention(p, pre, a, cfg):
    n, dc, dr = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                 cfg["qk_rope_head_dim"])
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    bsz, s, _ = a.shape
    cq = rms_norm(a @ p[pre + "q_a_proj.weight"].T,
                  p[pre + "q_a_layernorm.weight"], eps)
    q = (cq @ p[pre + "q_b_proj.weight"].T).reshape(bsz, s, n, dc + dr)
    down = a @ p[pre + "kv_a_proj_with_mqa.weight"].T
    ckv = rms_norm(down[..., :rank], p[pre + "kv_a_layernorm.weight"], eps)
    kv = (ckv @ p[pre + "kv_b_proj.weight"].T).reshape(bsz, s, n, -1)
    kc, v = kv[..., :dc], kv[..., dc:]
    qr = rotate(q[..., dc:], cfg["rope_theta"])
    kr = rotate(down[:, :, None, rank:], cfg["rope_theta"])
    q = jnp.concatenate([q[..., :dc], qr], -1)
    k = jnp.concatenate([kc, jnp.broadcast_to(kr, (bsz, s, n, dr))], -1)
    k_pos = jnp.arange(s)[None, :]

    @jax.checkpoint
    def block(qb, k, v, q0):
        q_pos = q0 + jnp.arange(qb.shape[1])[:, None]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) \
            / jnp.sqrt(jnp.asarray(dc + dr, qb.dtype))
        scores = jnp.where((k_pos <= q_pos)[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)

    out = [block(q[:, q0:q0 + QUERY_BLOCK], k, v, q0)
           for q0 in range(0, s, QUERY_BLOCK)]
    return jnp.concatenate(out, 1).reshape(bsz, s, -1) \
        @ p[pre + "o_proj.weight"].T


# ---------------------------------------------------------- feed-forward

def dense_mlp(p, pre, u):
    return silu_gated(u, p[pre + "gate_proj.weight"].T,
                      p[pre + "up_proj.weight"].T,
                      p[pre + "down_proj.weight"].T)


def route(p, pre, u, cfg, ids0=None):
    """(picked (..., k) ids over ALL experts, their weights); ``ids0`` the
    0-based tokens of ``u``'s positions."""
    training = cfg.get("training", {})
    s = jax.nn.sigmoid((u @ p[pre + "gate.weight"]).astype(jnp.float32))
    if training.get("router_gradient", "full") == "none":
        s = jax.lax.stop_gradient(s)
    if training.get("router_picks", "scores") == "token_id":
        picked = p[pre + "gate.pick_table"][ids0].astype(jnp.int32)
    else:
        _, picked = jax.lax.top_k(
            s + p[pre + "gate.e_score_correction_bias"],
            cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, picked, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return picked, (w * cfg["routed_scaling_factor"]).astype(u.dtype)


def moe(p, pre, u, cfg, ids0=None):
    picked, w = route(p, pre, u, cfg, ids0)
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

def layer(p, i, x, cfg, dense, ids0=None):
    """One layer: (its output, its router's picks or None)."""
    pre, eps = f"model.layers.{i}.", cfg["rms_norm_eps"]
    x = x + attention(p, pre + "self_attn.", rms_norm(
        x, p[pre + "input_layernorm.weight"], eps), cfg)
    m = rms_norm(x, p[pre + "post_attention_layernorm.weight"], eps)
    if dense:
        f, picked = dense_mlp(p, pre + "mlp.", m), None
    else:
        f, picked = moe(p, pre + "mlp.", m, cfg, ids0)
    return x + f, picked


def streams(p, ids0, cfg, dtype=jnp.float32):
    """(the main stream after the final norm (B, T, hidden); the prediction
    module's after its own norm (B, T-1, hidden), or None without one; the
    routers' picks of each expert layer, the module's last). ``dtype``:
    float32, the reference; a lower one gives the reading that a tolerance
    has to keep out."""
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), p)
    eps, depth = cfg["rms_norm_eps"], cfg["num_hidden_layers"]
    emb = p["model.embed_tokens.weight"][ids0]
    x, picks = emb, []
    for i in range(depth):
        x, picked = layer(p, i, x, cfg, i < cfg["first_k_dense_replace"],
                          ids0)
        if picked is not None:
            picks.append(picked)
    main = rms_norm(x, p["model.norm.weight"], eps)
    if not cfg.get("num_nextn_predict_layers", 0):
        return main, None, picks
    pre = f"model.layers.{depth}."
    z = jnp.concatenate(
        [rms_norm(emb[:, 1:], p[pre + "enorm.weight"], eps),
         rms_norm(x[:, :-1], p[pre + "hnorm.weight"], eps)], -1) \
        @ p[pre + "eh_proj.weight"].T
    z, picked = layer(p, depth, z, cfg, False, ids0[:, 1:])
    picks.append(picked)
    return main, rms_norm(z, p[pre + "shared_head.norm.weight"], eps), picks


def cross_entropy(p, x, targets0, dtype):
    lp = jax.nn.log_softmax(x @ p["lm_head.weight"].astype(dtype).T, -1)
    nll = -jnp.take_along_axis(lp, targets0[..., None], -1)
    return jnp.mean(nll.astype(jnp.float32))


def losses(p, ids0, targets0, cfg, dtype=jnp.float32):
    """(L_main, L_mtp or 0, picks): mean cross-entropies over the held
    slice, position i of the main stream against ``targets0[i]`` (token
    i + 1) and of the module's against ``targets0[i + 1]``."""
    main, nxt, picks = streams(p, ids0, cfg, dtype)
    second = jnp.zeros((), jnp.float32) if nxt is None else \
        cross_entropy(p, nxt, targets0[:, 1:], dtype)
    return cross_entropy(p, main, targets0, dtype), second, picks


def loss(p, ids0, targets0, cfg, dtype=jnp.float32):
    """(``L_main + training.mtp_loss_weight * L_mtp``, picks)."""
    first, second, picks = losses(p, ids0, targets0, cfg, dtype)
    return first + cfg["training"]["mtp_loss_weight"] * second, picks


def loss_and_grad_norm(p, ids0, targets0, cfg, dtype=jnp.float32):
    """(loss, global L2 norm of its gradient, picks). The selection bias
    takes no gradient: it is added to the scores inside ``top_k`` only,
    whose picks are integers."""
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
