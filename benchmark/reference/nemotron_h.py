"""Plain reference of the Nemotron-H hybrid decoder LM (``NemotronHFor
CausalLM``, ``model_type`` ``nemotron_h``): float32 ``jax.numpy``, no
kernels, no chunked scan, no sorted dispatch. Callers wrap it in
``jax.default_matmul_precision("highest")``.

Every block is ``x <- x + mixer(RMSNorm(x))`` with ONE mixer, chosen by the
block's character in ``hybrid_override_pattern``:

``M`` Mamba-2: ``[z | xBC | dt] = u W_in``; ``xBC = silu(conv(xBC) + b)``
  (causal, depthwise, ``conv_kernel`` wide); ``x, B, C = split(xBC)``;
  ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``; the recurrence
  PER TOKEN, ``H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t``,
  ``y_t = H_t C_t + D x_t`` (head h reads group ``h // (heads/groups)``,
  ``H_0 = 0``); ``y = GroupRMSNorm(y * silu(z)) * w``; ``y W_out``.
``E`` mixture of experts: ``s = sigmoid(u W_r)``; the top
  ``num_experts_per_tok`` of ``s + b``; ``w_i = routed_scaling_factor *
  s_i / (sum_picked s + 1e-20)``; ``sum_{i picked and held} w_i W2_i
  relu(W1_i u)^2 + W2_s relu(W1_s u)^2``. The experts are a loop over the
  held ids with a dense (T,) weight each: what the absent experts would
  add is left out, as in the program.
``*`` attention: grouped-query causal softmax attention, ``head_dim`` its
  own size, no bias, NO positional term (Nemotron-H applies none).

Final RMSNorm, an UNTIED head over the held slice of the vocabulary, mean
next-token cross-entropy.

Parameters are a dict under the public checkpoint's names
(``backbone.layers.<i>.mixer.in_proj.weight`` ...). Departures in LAYOUT
only, so that the program's arrays are read without a copy: the routed
experts are stacked, ``mixer.experts.up_proj`` (held, hidden, width) and
``mixer.experts.down_proj`` (held, width, hidden), held experts in the
order of ``held_experts``; ``mixer.shared_experts.*`` and
``mixer.gate.weight`` are (in, out); attention's q;k;v are one
``mixer.qkv_proj.weight`` of stacked rows. The per-token recurrence keeps
a (heads, head_dim, state) float32 state a token for its backward, so it
runs as blocks of ``SCAN_BLOCK`` tokens under ``jax.checkpoint``: the same
per-token steps, the states inside a block recomputed in the backward.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

SCAN_BLOCK = 64


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def held_experts(cfg):
    """ids of the routed experts this chip holds: the first
    ``n_routed_experts`` (the file's count of HELD experts) of the
    router's outputs."""
    return tuple(range(cfg["n_routed_experts"]))


# ---------------------------------------------------------------- the mixers

def selective_scan(x, dt, a, b, c):
    """The recurrence token by token. ``x`` (B, L, H, P), ``dt`` (B, L, H),
    ``a`` (H,), ``b``/``c`` (B, L, G, N) -> (B, L, H, P)."""
    bsz, length, h, p = x.shape
    g, n = b.shape[2:]
    b = jnp.repeat(b, h // g, axis=2)           # head h reads group h // r
    c = jnp.repeat(c, h // g, axis=2)

    def token(state, inp):
        x_t, dt_t, b_t, c_t = inp               # (B,H,P) (B,H) (B,H,N) x2
        keep = jnp.exp(dt_t * a)[..., None, None]
        state = keep * state + (dt_t[..., None] * x_t)[..., None] \
            * b_t[..., None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    @jax.checkpoint
    def block(state, inp):
        return jax.lax.scan(token, state, inp)

    pad = (-length) % SCAN_BLOCK
    seq = [jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)]
    if pad:     # dt = 0: the state passes unchanged, outputs are cut off
        seq = [jnp.pad(t, [(0, pad)] + [(0, 0)] * (t.ndim - 1)) for t in seq]
    seq = [t.reshape((-1, SCAN_BLOCK) + t.shape[1:]) for t in seq]
    _, y = jax.lax.scan(block, jnp.zeros((bsz, h, p, n), x.dtype),
                        tuple(seq))
    y = y.reshape((-1,) + y.shape[2:])[:length]
    return jnp.moveaxis(y, 0, 1)


def mamba2(p, pre, u, cfg):
    h, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n, k = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    d_inner = h * hd
    bsz, length, _ = u.shape
    zxbcdt = u @ p[pre + "in_proj.weight"].T
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:2 * d_inner + 2 * g * n]
    dt = zxbcdt[..., 2 * d_inner + 2 * g * n:]
    w = p[pre + "conv1d.weight"]                # (conv_dim, k)
    padded = jnp.pad(xbc, [(0, 0), (k - 1, 0), (0, 0)])
    conv = sum(padded[:, j:j + length] * w[:, j] for j in range(k))
    xbc = jax.nn.silu(conv + p[pre + "conv1d.bias"])
    x = xbc[..., :d_inner].reshape(bsz, length, h, hd)
    b = xbc[..., d_inner:d_inner + g * n].reshape(bsz, length, g, n)
    c = xbc[..., d_inner + g * n:].reshape(bsz, length, g, n)
    dt = jax.nn.softplus(dt + p[pre + "dt_bias"])
    a = -jnp.exp(p[pre + "A_log"])
    y = selective_scan(x, dt, a, b, c) + p[pre + "D"][:, None] * x
    y = y.reshape(bsz, length, d_inner) * jax.nn.silu(z)
    yg = y.reshape(bsz, length, g, d_inner // g)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True)
                            + cfg["layer_norm_epsilon"])
    y = yg.reshape(bsz, length, d_inner) * p[pre + "norm.weight"]
    return y @ p[pre + "out_proj.weight"].T


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def route(p, pre, u, cfg):
    """(picked (..., k) ids over ALL experts, their weights)."""
    s = jax.nn.sigmoid(u @ p[pre + "gate.weight"])
    _, picked = jax.lax.top_k(
        s + p[pre + "gate.e_score_correction_bias"],
        cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, picked, -1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return picked, w * cfg["routed_scaling_factor"]


def moe(p, pre, u, cfg):
    picked, w = route(p, pre, u, cfg)
    out = relu2(u @ p[pre + "shared_experts.up_proj.weight"]) \
        @ p[pre + "shared_experts.down_proj.weight"]
    for j, eid in enumerate(held_experts(cfg)):
        mine = jnp.sum(jnp.where(picked == eid, w, 0.0), -1, keepdims=True)
        out = out + mine * (relu2(u @ p[pre + "experts.up_proj"][j])
                            @ p[pre + "experts.down_proj"][j])
    return out, picked


def attention(p, pre, u, cfg):
    h, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    bsz, s, _ = u.shape
    qkv = u @ p[pre + "qkv_proj.weight"].T
    q = qkv[..., :h * d].reshape(bsz, s, h, d)
    k = qkv[..., h * d:(h + kv) * d].reshape(bsz, s, kv, d)
    v = qkv[..., (h + kv) * d:].reshape(bsz, s, kv, d)
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return ctx.reshape(bsz, s, h * d) @ p[pre + "o_proj.weight"].T


# ------------------------------------------------------------------ the model

def hidden(p, ids0, cfg, dtype=jnp.float32):
    """(final hidden states (B, S, E), the routers' picks of each ``E``
    block in order). ``dtype``: float32, the reference; a lower one gives
    the reading that a tolerance has to keep out."""
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), p)
    x = p["backbone.embeddings.weight"][ids0]
    picks = []
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        pre = f"backbone.layers.{i}."
        u = rms_norm(x, p[pre + "norm.weight"], cfg["layer_norm_epsilon"])
        if kind == "M":
            y = mamba2(p, pre + "mixer.", u, cfg)
        elif kind == "E":
            y, picked = moe(p, pre + "mixer.", u, cfg)
            picks.append(picked)
        elif kind == "*":
            y = attention(p, pre + "mixer.", u, cfg)
        else:
            raise ValueError(f"unknown block kind {kind!r} in the pattern")
        x = x + y
    x = rms_norm(x, p["backbone.norm_f.weight"], cfg["layer_norm_epsilon"])
    return x, picks


def loss(p, ids0, targets0, cfg, dtype=jnp.float32):
    """(mean next-token cross-entropy over the held slice, picks)."""
    x, picks = hidden(p, ids0, cfg, dtype)
    lp = jax.nn.log_softmax(x @ p["lm_head.weight"].astype(dtype).T, -1)
    nll = -jnp.take_along_axis(lp, targets0[..., None], -1)
    return jnp.mean(nll.astype(jnp.float32)), picks


def loss_and_grad_norm(p, ids0, targets0, cfg, dtype=jnp.float32):
    """(loss, global L2 norm of its gradient, picks)."""
    (val, picks), g = jax.value_and_grad(loss, has_aux=True)(
        p, ids0, targets0, cfg, dtype)
    sq = sum(jnp.sum(x.astype(jnp.float32) ** 2)
             for x in jax.tree_util.tree_leaves(g))
    return val, jnp.sqrt(sq), picks


def pick_stats(picks, cfg):
    """What the routers did on one batch: picks per held expert (mean and
    max over experts and ``E`` blocks) and the share of all picks that went
    to experts held elsewhere."""
    held = jnp.asarray(held_experts(cfg))
    per = jnp.stack([jnp.sum(pk[..., None] == held, axis=tuple(
        range(pk.ndim))) for pk in picks])                  # (blocks, held)
    total = sum(pk.size for pk in picks)
    return {"picks_per_held_expert_mean": float(jnp.mean(per)),
            "picks_per_held_expert_max": int(jnp.max(per)),
            "absent_pick_share": float(1.0 - jnp.sum(per) / total)}
