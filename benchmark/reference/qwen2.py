"""Plain reference of the Qwen2 decoder LM (``Qwen2ForCausalLM``): float32
``jax.numpy``, no kernels, no cache, no batching tricks, following the
public modelling code: RMSNorm -> q/k/v projections WITH bias -> rotate-half
RoPE (``rope_theta``) -> grouped-query causal softmax attention -> output
projection without bias -> residual; RMSNorm -> SwiGLU MLP (silu(gate) * up
-> down, no bias) -> residual; final RMSNorm; logits through the TIED
embedding matrix. Callers wrap it in ``jax.default_matmul_precision(
"highest")``: a TPU multiplies float32 in lower precision otherwise.

Parameters are a dict under the names of ``interop.state_dict``'s torch
convention (``embedding.weight``, ``encoder.layers.<i>.self_attn.
in_proj_weight`` = q;k;v rows stacked, ``linear1`` = gate, ``linear_gate`` =
up, ``linear2`` = down, ``encoder.norm.weight``); token ids are 0-based.
No departure from the published block is known.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, theta):
    """x: (B, S, H, D); HF's rotate-half convention."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def decoder_layer(p, pre, x, cfg):
    e = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    d = e // h
    eps = cfg["rms_norm_eps"]
    b, s, _ = x.shape
    y = rms_norm(x, p[pre + "norm1.weight"], eps)
    qkv = y @ p[pre + "self_attn.in_proj_weight"].T \
        + p[pre + "self_attn.in_proj_bias"]
    q = qkv[..., :e].reshape(b, s, h, d)
    k = qkv[..., e:e + kv * d].reshape(b, s, kv, d)
    v = qkv[..., e + kv * d:].reshape(b, s, kv, d)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    x = x + attn.reshape(b, s, e) @ p[pre + "self_attn.out_proj.weight"].T
    y = rms_norm(x, p[pre + "norm2.weight"], eps)
    gate = jax.nn.silu(y @ p[pre + "linear1.weight"].T)
    up = y @ p[pre + "linear_gate.weight"].T
    return x + (gate * up) @ p[pre + "linear2.weight"].T


def logits(p, ids0, cfg):
    """(B, S) 0-based ids -> (B, S, V) float32 logits."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    x = p["embedding.weight"][ids0]
    for i in range(cfg["num_hidden_layers"]):
        x = decoder_layer(p, f"encoder.layers.{i}.", x, cfg)
    x = rms_norm(x, p["encoder.norm.weight"], cfg["rms_norm_eps"])
    return x @ p["embedding.weight"].T


def loss(p, ids0, targets0, cfg):
    """Mean next-token cross-entropy over every position."""
    lg = logits(p, ids0, cfg)
    lp = jax.nn.log_softmax(lg, -1)
    return -jnp.mean(jnp.take_along_axis(lp, targets0[..., None], -1))


def loss_and_grad_norm(p, ids0, targets0, cfg):
    val, g = jax.value_and_grad(loss)(p, ids0, targets0, cfg)
    sq = sum(jnp.sum(x.astype(jnp.float32) ** 2)
             for x in jax.tree_util.tree_leaves(g))
    return val, jnp.sqrt(sq)
