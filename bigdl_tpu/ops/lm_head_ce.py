"""Fused LM-head cross-entropy: logits are never materialised.

The standard causal-LM tail — ``TimeDistributed(Linear(E, V)) -> LogSoftMax
-> ClassNLL`` — materialises the (B*S, V) logits (plus the normalised
log-probs and their cotangent) in HBM. At B*S = 16K, V = 32K that is ~1 GB
per array per pass, and an on-chip probe measured the head at **54% of the
whole training step** (PERF.md round 3). The reference has no analogue (its
``nn/LogSoftMax.scala`` + ``ClassNLLCriterion.scala`` pair materialises the
full activation just the same — at reference scale V is tiny).

This op computes ``mean(logsumexp(h @ W^T + b) - logit[target])`` by a
``lax.scan`` over VOCAB CHUNKS with an online (flash-style) logsumexp:

- forward: per chunk, one (N, C) matmul + running (max, sumexp, target-logit)
  — only the (N, C) chunk is ever live;
- backward (custom VJP): recompute each chunk's logits from the saved
  row logsumexp, form ``softmax - onehot`` in place, and accumulate
  ``dh`` and the per-chunk rows of ``dW``/``db``.

Matmuls run in the inputs' compute dtype (bf16 under the mixed policy);
softmax statistics and accumulations are fp32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_NEG = -1e30  # effective -inf that survives exp without NaNs


def _pad_vocab(w: jax.Array, b: jax.Array, chunk: int):
    v = w.shape[0]
    n_chunks = -(-v // chunk)
    pad = n_chunks * chunk - v
    if pad:
        w = jnp.pad(w, ((0, pad), (0, 0)))
        # padded rows get bias -inf so exp() contributes 0 mass
        b = jnp.pad(b, (0, pad), constant_values=_NEG)
    return w, b, n_chunks


def _chunk_logits(h, w, b, c, chunk):
    """(N, C) logits of chunk c in compute dtype, fp32 out."""
    w_c = lax.dynamic_slice_in_dim(w, c * chunk, chunk, axis=0)
    b_c = lax.dynamic_slice_in_dim(b, c * chunk, chunk, axis=0)
    logits = jnp.matmul(h, w_c.T.astype(h.dtype))
    return logits.astype(jnp.float32) + b_c.astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _lm_head_ce(h, w, b, valid, tgt0, chunk):
    """Per-row CE over valid rows; returns (loss_sum, n_valid, lse)."""
    out, _ = _lm_head_ce_fwd(h, w, b, valid, tgt0, chunk)
    return out


# the scope names both scans in the trace (under custom_vjp the backward
# is traced on its own, so it carries the scope itself)
@jax.named_scope("lm_head_ce")
def _lm_head_ce_fwd(h, w, b, valid, tgt0, chunk):
    n = h.shape[0]
    wp, bp, n_chunks = _pad_vocab(w, b, chunk)

    def body(carry, c):
        m, s, zt = carry
        logits = _chunk_logits(h, wp, bp, c, chunk)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[:, None]), axis=-1)
        idx = tgt0 - c * chunk
        in_c = (idx >= 0) & (idx < chunk)
        z = jnp.take_along_axis(
            logits, jnp.clip(idx, 0, chunk - 1)[:, None], axis=1)[:, 0]
        zt = jnp.where(in_c, z, zt)
        return (m_new, s, zt), None

    init = (jnp.full((n,), _NEG, jnp.float32),
            jnp.zeros((n,), jnp.float32),
            jnp.full((n,), _NEG, jnp.float32))
    (m, s, zt), _ = lax.scan(body, init, jnp.arange(n_chunks))
    lse = m + jnp.log(jnp.maximum(s, 1e-37))
    per_row = jnp.where(valid, lse - zt, 0.0)
    loss_sum = jnp.sum(per_row)
    n_valid = jnp.sum(valid.astype(jnp.float32))
    return (loss_sum, n_valid, lse), (h, w, b, valid, tgt0, lse)


@jax.named_scope("lm_head_ce")
def _lm_head_ce_bwd(chunk, res, cts):
    h, w, b, valid, tgt0, lse = res
    g_sum, _, g_lse = cts  # cotangents for (loss_sum, n_valid, lse)
    wp, bp, n_chunks = _pad_vocab(w, b, chunk)
    n, e = h.shape
    vmask = valid.astype(jnp.float32)
    # d loss_sum / d logits_c = (softmax - onehot) * valid; plus the lse
    # cotangent's softmax term (lse is also an output — g_lse is zero in
    # the criterion path but keeps the op a correct VJP in general).
    row_g = g_sum * vmask + g_lse

    def body(dh, c):
        logits = _chunk_logits(h, wp, bp, c, chunk)
        p = jnp.exp(logits - lse[:, None])
        idx = tgt0 - c * chunk
        onehot = ((jnp.arange(chunk)[None, :] == idx[:, None])
                  .astype(jnp.float32))
        g_logits = p * row_g[:, None] - onehot * (g_sum * vmask)[:, None]
        w_c = lax.dynamic_slice_in_dim(wp, c * chunk, chunk, axis=0)
        gl = g_logits.astype(h.dtype)
        dh = dh + jnp.matmul(gl, w_c.astype(h.dtype)).astype(jnp.float32)
        dw_c = jnp.matmul(gl.T, h).astype(jnp.float32)
        return dh, (dw_c, jnp.sum(g_logits, axis=0))

    dh, (dw_chunks, db_chunks) = lax.scan(
        body, jnp.zeros((n, e), jnp.float32), jnp.arange(n_chunks))
    v = w.shape[0]
    dw = dw_chunks.reshape(n_chunks * chunk, e)[:v]
    db = db_chunks.reshape(n_chunks * chunk)[:v]
    return (dh.astype(h.dtype), dw.astype(w.dtype), db.astype(b.dtype),
            np.zeros(valid.shape, dtype=jax.dtypes.float0),
            np.zeros(tgt0.shape, dtype=jax.dtypes.float0))


_lm_head_ce.defvjp(_lm_head_ce_fwd, _lm_head_ce_bwd)


def fused_lm_head_ce(hidden: jax.Array, weight: jax.Array,
                     bias: Optional[jax.Array], targets: jax.Array, *,
                     chunk: int = 16384, size_average: bool = True,
                     ignore_index: Optional[int] = None) -> jax.Array:
    """Cross-entropy of ``hidden @ weight.T + bias`` against 1-based targets.

    ``hidden``: (..., E); ``weight``: (V, E); ``targets``: hidden's leading
    shape, values in 1..V (any numeric dtype). Rows whose target equals
    ``ignore_index`` contribute nothing (and don't count toward the mean).
    Numerically equal to ``ClassNLL(LogSoftMax(logits), targets)`` without
    ever materialising (N, V) logits.
    """
    e = hidden.shape[-1]
    h2 = hidden.reshape(-1, e)
    tgt = targets.reshape(-1)
    tgt0 = tgt.astype(jnp.int32) - 1
    if ignore_index is not None:
        valid = (tgt.astype(jnp.int32) != int(ignore_index))
    else:
        valid = jnp.ones(tgt0.shape, bool)
    if bias is None:
        bias = jnp.zeros((weight.shape[0],), weight.dtype)
    chunk = min(int(chunk), weight.shape[0])
    loss_sum, n_valid, _ = _lm_head_ce(h2, weight, bias, valid, tgt0, chunk)
    if size_average:
        return loss_sum / jnp.maximum(n_valid, 1.0)
    return loss_sum
