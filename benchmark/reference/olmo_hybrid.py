"""Plain reference of the Olmo-Hybrid decoder LM (Allen AI Olmo-Hybrid-7B,
``model_type`` ``olmo_hybrid``): float32 ``jax.numpy``, no kernels, the
delta rule TOKEN BY TOKEN, attention as a plain softmax. Callers wrap it in
``jax.default_matmul_precision("highest")``. It shares no code with
``bigdl_tpu/``.

``h`` is the stream (B, T, hidden); every RMSNorm has ``rms_norm_eps`` and
a learned weight; no product has a bias.

Embedding: ``h = E[ids]``.

Layer ``l``: ``a = h + norm_attn(Mixer(h))``, ``h' = a + norm_ff(MLP(a))``:
the family norms a mixer's OUTPUT and not its input (OLMo 2,
arXiv:2501.00656).

  ``layer_types[l]`` ``linear_attention``, the gated delta rule (Yang et
  al., arXiv:2412.06464) over ``H = linear_num_value_heads`` heads of ``d_k
  = linear_key_head_dim`` and ``d_v = linear_value_head_dim``::

    q = silu(conv(h W_q)), k = silu(conv(h W_k)), v = silu(conv(h W_v))
        conv_t = sum_{j<K} w[:, j] * x_{t-(K-1)+j}, K =
        linear_conv_kernel_dim (depthwise, causal, zeros before the start,
        no bias)
    a head at a time: q_t <- q_t / |q_t|_2 * d_k^-1/2, k_t <- k_t / |k_t|_2
    beta_t = sigmoid(h_t W_b) (x 2 where linear_allow_neg_eigval)
    g_t = -exp(A_log) * softplus(h_t W_a + dt_bias)
    S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
        (d_k x d_v, zero at the start of a record)
    o_t = S_t^T q_t
    Mixer = concat_heads(RMSNorm_{d_v}(o_t) * w * silu(h_t W_g)) W_o

  ``layer_types[l]`` ``full_attention``: ``q = h W_q``, ``k = h W_k``, ``v
  = h W_v``, ``num_attention_heads`` heads of ``head_dim`` each (as many
  key/value heads); RMSNorm over the WHOLE q and the whole k projection
  (a learned weight a column; the mean square runs over the heads held,
  ``whole`` hands in a deployment's all-reduced one); no positional term
  (``rope_theta`` null); every key ``j <= i`` visible; ``o = softmax(q k^T
  / sqrt(head_dim)) v``; ``Mixer = o W_o``.

  ``MLP``: SwiGLU, ``(silu(a W_gate) * (a W_up)) W_down``.

Tail: a final RMSNorm, an untied head over the held rows of the
vocabulary, mean next-token cross-entropy over them.

Departures from the published description. In WHAT is computed: the heads
of both mixer kinds are the chip's SHARE (the file's head counts; each
out-projection gives its heads' part of the sum and nothing stands in for
the rest), and ``L2_EPS`` stands under the root of the two L2 norms, as in
the public kernels. In LAYOUT only, so that the program's arrays are read
without a copy: the six in-projections of a linear layer are one
``linear_attn.in_proj.weight`` of stacked rows ``[q; k; v; g; b; a]`` and
its three convolutions one ``linear_attn.conv.weight`` (rows q, k, v; (.,
K) without the singleton axis); q;k;v of a full layer are one
``self_attn.qkv_proj.weight``. In HOW it is evaluated, never in its value:
the recurrence is a ``lax.scan`` over positions in blocks of ``SCAN_BLOCK``
under ``jax.checkpoint`` (T / SCAN_BLOCK states are kept for the backward,
not T), attention runs in blocks of ``QUERY_BLOCK`` queries against all
keys, each under ``jax.checkpoint``, and so does each whole layer (its
backward computes the layer again: the four MLPs' (T, 11,008) float32
intermediates are 13.8 GB of temporaries otherwise), so that 1 x 8,192
tokens fit on the chip beside the model.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
SCAN_BLOCK = 64
L2_EPS = 1e-6


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def swiglu(u, gate, up, down):
    return (jax.nn.silu(u @ gate.T) * (u @ up.T)) @ down.T


# ----------------------------------------------------------- the delta rule

def delta_rule(q, k, v, g, beta):
    """``o`` (B, T, H, d_v) of the recurrence, token by token, from ``q``,
    ``k`` (B, T, H, d_k) as it reads them, ``v`` (B, T, H, d_v), ``g`` and
    ``beta`` (B, T, H); the state in ``v``'s dtype."""
    bsz, length, h, dk = q.shape
    dv = v.shape[-1]
    blk = min(SCAN_BLOCK, length)
    if length % blk:
        raise ValueError(f"the reference takes whole blocks of {blk} "
                         f"positions")

    def step(state, inp):
        q_t, k_t, v_t, g_t, b_t = inp       # (B, H, d), (B, H)
        held = jnp.einsum("bhd,bhde->bhe", k_t, state)
        state = state - b_t[..., None, None] * k_t[..., None] \
            * held[..., None, :]
        state = jnp.exp(g_t)[..., None, None] * state \
            + b_t[..., None, None] * k_t[..., None] * v_t[..., None, :]
        return state, jnp.einsum("bhde,bhd->bhe", state, q_t)

    @jax.checkpoint
    def block(state, inp):
        return jax.lax.scan(step, state, inp)

    by_time = tuple(jnp.moveaxis(t, 1, 0).reshape(
        (length // blk, blk) + t.shape[:1] + t.shape[2:])
        for t in (q, k, v, g.astype(v.dtype), beta.astype(v.dtype)))
    _, o = jax.lax.scan(block, jnp.zeros((bsz, h, dk, dv), v.dtype), by_time)
    return jnp.moveaxis(o.reshape(length, bsz, h, dv), 0, 1)


def causal_conv(x, w):
    """Depthwise causal convolution of (B, T, C) with taps (C, K), written
    as the K-term sum it is."""
    k, length = w.shape[-1], x.shape[1]
    out = jnp.zeros_like(x)
    for j in range(k):
        back = k - 1 - j                # tap j reads the position t - back
        out = out + w[:, j] * jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :length - back]], axis=1)
    return out


def linear_heads(cfg):
    h = cfg["linear_num_value_heads"]
    if cfg["linear_num_key_heads"] != h:
        raise ValueError("the reference takes as many key as value heads")
    return h, cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]


def recurrence_inputs(p, pre, u, cfg):
    """(q, k, v, g, beta, the output gate's input) of a linear layer from
    the stream ``u``."""
    h, dk, dv = linear_heads(cfg)
    bsz, length, _ = u.shape
    proj = u @ p[pre + "in_proj.weight"].T
    wide = 2 * h * dk + h * dv
    qkv = jax.nn.silu(causal_conv(proj[..., :wide], p[pre + "conv.weight"]))
    q = qkv[..., :h * dk].reshape(bsz, length, h, dk)
    k = qkv[..., h * dk:2 * h * dk].reshape(bsz, length, h, dk)
    v = qkv[..., 2 * h * dk:].reshape(bsz, length, h, dv)
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS) \
        / jnp.sqrt(jnp.asarray(dk, q.dtype))
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
    z = proj[..., wide:wide + h * dv]
    b = proj[..., wide + h * dv:wide + h * dv + h]
    a = proj[..., wide + h * dv + h:]
    beta = jax.nn.sigmoid(b) * (2.0 if cfg["linear_allow_neg_eigval"]
                                else 1.0)
    g = -jnp.exp(p[pre + "A_log"]) * jax.nn.softplus(a + p[pre + "dt_bias"])
    return q, k, v, g, beta, z


def gated_delta_net(p, pre, u, cfg):
    """The linear-attention mixer on the stream ``u``."""
    q, k, v, g, beta, z = recurrence_inputs(p, pre, u, cfg)
    o = rms_norm(delta_rule(q, k, v, g, beta), p[pre + "o_norm.weight"],
                 cfg["rms_norm_eps"])
    y = o.reshape(z.shape) * jax.nn.silu(z)
    return y @ p[pre + "o_proj.weight"].T


# -------------------------------------------------------------- attention

def qk_mean_squares(p, pre, u, cfg):
    """The mean square of the q and of the k projection over the heads
    held, (B, T, 1) each: what the chips sharing a layer's heads average
    before the norm."""
    wide = cfg["num_attention_heads"] * cfg["head_dim"]
    qkv = u @ p[pre + "qkv_proj.weight"].T
    q, k = qkv[..., :wide], qkv[..., wide:2 * wide]
    return (jnp.mean(q * q, -1, keepdims=True),
            jnp.mean(k * k, -1, keepdims=True))


def attention(p, pre, u, cfg, whole=None):
    """The attention mixer on the stream ``u``: ``o W_o``. ``whole``: the
    two mean squares to norm by, where they are not this share's own."""
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    if cfg["num_key_value_heads"] != h:
        raise ValueError("the reference takes as many key/value heads as "
                         "query heads")
    eps = cfg["rms_norm_eps"]
    bsz, s, _ = u.shape
    qkv = u @ p[pre + "qkv_proj.weight"].T
    q, k, v = (qkv[..., i * h * d:(i + 1) * h * d] for i in range(3))
    ms_q, ms_k = qk_mean_squares(p, pre, u, cfg) if whole is None else whole
    q = q * jax.lax.rsqrt(ms_q + eps) * p[pre + "q_norm.weight"]
    k = k * jax.lax.rsqrt(ms_k + eps) * p[pre + "k_norm.weight"]
    q, k, v = (t.reshape(bsz, s, h, d) for t in (q, k, v))
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


# ------------------------------------------------------------------ the model

def layer(p, i, h, cfg):
    pre, eps = f"model.layers.{i}.", cfg["rms_norm_eps"]
    if cfg["layer_types"][i] == "linear_attention":
        mixed = gated_delta_net(p, pre + "linear_attn.", h, cfg)
    else:
        mixed = attention(p, pre + "self_attn.", h, cfg)
    a = h + rms_norm(mixed, p[pre + "post_attention_layernorm.weight"], eps)
    ff = swiglu(a, p[pre + "mlp.gate_proj.weight"],
                p[pre + "mlp.up_proj.weight"],
                p[pre + "mlp.down_proj.weight"])
    return a + rms_norm(ff, p[pre + "post_feedforward_layernorm.weight"],
                        eps)


def hidden(p, ids0, cfg, dtype=jnp.float32):
    """Final hidden states (B, T, hidden). ``dtype``: float32, the
    reference; a lower one gives the reading that a tolerance has to keep
    out (the recurrent state is then held in it too)."""
    p = {k: v.astype(dtype) for k, v in p.items()}
    x = p["model.embed_tokens.weight"][ids0]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(lambda p, x, i=i: layer(p, i, x, cfg))(p, x)
    return rms_norm(x, p["model.norm.weight"], cfg["rms_norm_eps"])


def loss(p, ids0, targets0, cfg, dtype=jnp.float32):
    """Mean next-token cross-entropy over the held slice."""
    x = hidden(p, ids0, cfg, dtype)
    lp = jax.nn.log_softmax(x @ p["lm_head.weight"].astype(dtype).T, -1)
    nll = -jnp.take_along_axis(lp, targets0[..., None], -1)
    return jnp.mean(nll.astype(jnp.float32))


def loss_and_grad_norm(p, ids0, targets0, cfg, dtype=jnp.float32):
    """(loss, global L2 norm of its gradient)."""
    val, g = jax.value_and_grad(
        lambda q: loss(q, ids0, targets0, cfg, dtype))(p)
    sq = sum(jnp.sum(x.astype(jnp.float32) ** 2)
             for x in jax.tree_util.tree_leaves(g))
    return val, jnp.sqrt(sq)
