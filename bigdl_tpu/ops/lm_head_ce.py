"""Fused LM-head cross-entropy: the loss and its gradients from ONE pass
over the logits, which are never materialised at (N, V).

The standard causal-LM tail — ``TimeDistributed(Linear(E, V)) -> LogSoftMax
-> ClassNLL`` — materialises the (B*S, V) logits, the normalised log-probs
and their cotangent in HBM: 2.5 GB an array in float32 at B*S = 4,096,
V = 151,936. The reference has no analogue (its ``nn/LogSoftMax.scala`` +
``ClassNLLCriterion.scala`` pair materialises the full activation just the
same — at reference scale V is tiny).

This op computes ``mean(logsumexp(h @ W^T + b) - logit[target])`` by a
``lax.scan`` over ROW TILES (tokens). A tile holds its whole row of logits,
so its logsumexp is final the moment the tile's product is done:

- under ``grad`` (the ``custom_vjp``'s forward rule): per tile, the logits
  product, the row max / sum / target logit, ``softmax - onehot`` formed
  while the tile is live, ``dh_t = g @ W`` and ``dW += g^T @ h_t``: three
  products, nothing recomputed. The residuals ARE the gradients for a unit
  cotangent; the backward rule scales them. (A frozen head, gradient wrt
  ``h`` alone, still pays for the ``dW`` it drops.)
- not differentiated: the same tiles, the logits product alone.
- with a weight a row (``row_weight``, float32, itself differentiable: an
  exit distribution over the passes of a looped decoder,
  ``nn.FusedLMHeadCriterion``): the loss is ``sum_r w_r l_r``, a unit
  cotangent's gradients cannot be scaled by ONE number afterwards, so the
  tile forms ``(softmax - onehot) * w_r`` while it is live and keeps the
  per-row losses ``l_r``: they ARE ``dL/dw_r``. One call takes the rows of
  every pass, so ``W`` is read and ``dW`` written once a tile, not once a
  pass. Without a weight nothing of this is traced.

Rows a tile come from the shapes (``rows_per_tile``): a tile's logits are
live memory, and ``dW`` is read and written once a tile, in the open (on a
v5e at T=4,096, V=151,936: 20.7 ms in one tile, 22.1 in two, 24.5 in four,
30.3 in eight, against 31.6 for two scans over vocabulary chunks; PERF.md
section 6, PR 28), so the fewest tiles that fit win. Matmuls run in the inputs' compute dtype (bf16
under the mixed policy) at the vocabulary's own size; softmax statistics
and the ``dW`` accumulation are fp32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bigdl_tpu.ops.scopes import under_scope

# what the logits of one row tile may hold, reckoned in float32 (XLA keeps the
# tile in the compute dtype: half of it under the bf16 policy): one tile at
# T=4,096, V=151,936 (2.49 GB) and at T=8,192, V=16,384 (537 MB)
_TILE_BYTES = 5 << 29


def rows_per_tile(n: int, v: int, chunk: Optional[int] = None) -> int:
    """Rows a tile: ``chunk`` clamped to the ``n`` rows where given, else an
    even split of ``n`` into the fewest tiles whose float32 logits each fit
    ``_TILE_BYTES``."""
    if chunk is not None:
        return max(1, min(int(chunk), n))
    return -(-n // max(1, -(-4 * n * v // _TILE_BYTES)))


def _tile(h, tgt0, valid, wr, w, b, grads):
    """One row tile: its loss sum (each row times ``wr`` where there is a
    weight a row), its rows' losses and, with ``grads``, (dh, dW, db) of
    that sum; ``w`` already in the compute dtype, ``b`` None or (V,)."""
    logits = jnp.matmul(h, w.T).astype(jnp.float32)
    if b is not None:
        logits = logits + b.astype(jnp.float32)
    m = jnp.max(logits, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1))
    # the target logit as a masked row sum: it rides the exp-and-sum pass,
    # where a gather would make XLA keep a float32 copy of the tile for it
    onehot = jnp.arange(w.shape[0])[None, :] == tgt0[:, None]
    zt = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    per_row = jnp.where(valid, lse - zt, 0.0)
    loss = jnp.sum(per_row if wr is None else per_row * wr)
    if not grads:
        return loss, per_row, (None, None, None)
    # d loss / d logits = (softmax - onehot) on valid rows, times the row's
    # weight where there is one
    on = valid[:, None]
    g = jnp.exp(logits - lse[:, None]) - onehot
    g = jnp.where(on, g if wr is None else g * wr[:, None], 0.0)
    gl = g.astype(h.dtype)
    dw = lax.dot_general(gl, h, (((0,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32)
    db = None if b is None else jnp.sum(g, axis=0)
    return loss, per_row, (jnp.matmul(gl, w), dw, db)


def _over_tiles(h, w, b, valid, tgt0, rows, grads, row_weight=None):
    """Scan ``_tile`` over tiles of ``rows`` rows (the last one padded with
    invalid rows): loss_sum, with ``row_weight`` the rows' losses (else
    None) and, with ``grads``, the (dh, dW, db) of the sum."""
    n, e = h.shape
    tiles = -(-n // rows)
    pad = tiles * rows - n

    def tiled(x):
        if pad:     # rows only, and only where ``rows`` does not divide
            x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((tiles, rows) + x.shape[1:])

    wc = w.astype(h.dtype)      # once, at its own V
    weighted = row_weight is not None

    def body(acc, x):
        loss, per_row, (dh, dw, db) = _tile(*x, wc, b, grads)
        return jax.tree.map(jnp.add, acc, (loss, dw, db)), \
            (dh, per_row if weighted else None)

    def zeros(x):
        if grads and x is not None:
            return jnp.zeros(x.shape, jnp.float32)

    (loss, dw, db), (dh, per_row) = lax.scan(
        body, (jnp.zeros((), jnp.float32), zeros(w), zeros(b)),
        (tiled(h), tiled(tgt0), tiled(valid),
         tiled(row_weight) if weighted else None))
    if grads:
        dh = dh.reshape(tiles * rows, e)[:n]
    if weighted:
        per_row = per_row.reshape(tiles * rows)[:n]
    return loss, per_row, (dh, dw, db)


def _count(form):
    # trace-time count, as bigdl_ssd_scan_total: which form a compiled
    # program holds
    from bigdl_tpu.telemetry import get_registry, instruments
    instruments(get_registry()).lm_head_ce_total.labels(form=form).inc()


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
@under_scope("lm_head_ce")
def _lm_head_ce(h, w, b, valid, tgt0, rows):
    """CE summed over the valid rows; ``b`` may be None."""
    _count("forward_only")
    return _over_tiles(h, w, b, valid, tgt0, rows, grads=False)[0]


# the scope names the head's operations in the trace: the primal enters it
# inside itself, so each rule carries it too (ops/scopes.py)
@under_scope("lm_head_ce")
def _lm_head_ce_fwd(h, w, b, valid, tgt0, rows):
    _count("one_pass")
    loss, _, grads = _over_tiles(h, w, b, valid, tgt0, rows, grads=True)
    return loss, (*jax.tree.map(lambda g, x: g.astype(x.dtype), grads,
                                (h, w, b)), valid, tgt0)


@under_scope("lm_head_ce")
def _lm_head_ce_bwd(rows, res, g_sum):
    *grads, valid, tgt0 = res
    return (*jax.tree.map(lambda g: (g * g_sum).astype(g.dtype),
                          tuple(grads)),
            np.zeros(valid.shape, dtype=jax.dtypes.float0),
            np.zeros(tgt0.shape, dtype=jax.dtypes.float0))


_lm_head_ce.defvjp(_lm_head_ce_fwd, _lm_head_ce_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
@under_scope("lm_head_ce")
def _lm_head_ce_weighted(h, w, b, row_weight, valid, tgt0, rows):
    """(``sum_r row_weight_r l_r`` over the valid rows, the rows' ``l_r``);
    ``b`` may be None."""
    _count("forward_only")
    return _over_tiles(h, w, b, valid, tgt0, rows, False, row_weight)[:2]


@under_scope("lm_head_ce")
def _lm_head_ce_weighted_fwd(h, w, b, row_weight, valid, tgt0, rows):
    _count("weighted_one_pass")
    loss, per_row, grads = _over_tiles(h, w, b, valid, tgt0, rows, True,
                                       row_weight)
    return (loss, per_row), (
        *jax.tree.map(lambda g, x: g.astype(x.dtype), grads, (h, w, b)),
        per_row, valid, tgt0)


@under_scope("lm_head_ce")
def _lm_head_ce_weighted_bwd(rows, res, cotangents):
    # the rows' own cotangent has no gradient formed for it (that would be
    # a second pass over the logits): fused_lm_head_ce hands the rows out
    # behind stop_gradient, so it is zero here
    g_sum, _ = cotangents
    *grads, per_row, valid, tgt0 = res
    return (*jax.tree.map(lambda g: (g * g_sum).astype(g.dtype),
                          tuple(grads)),
            g_sum * per_row,
            np.zeros(valid.shape, dtype=jax.dtypes.float0),
            np.zeros(tgt0.shape, dtype=jax.dtypes.float0))


_lm_head_ce_weighted.defvjp(_lm_head_ce_weighted_fwd,
                            _lm_head_ce_weighted_bwd)


def fused_lm_head_ce(hidden: jax.Array, weight: jax.Array,
                     bias: Optional[jax.Array], targets: jax.Array, *,
                     chunk: Optional[int] = None, size_average: bool = True,
                     ignore_index: Optional[int] = None,
                     row_weight: Optional[jax.Array] = None,
                     return_rows: bool = False):
    """Cross-entropy of ``hidden @ weight.T + bias`` against 1-based targets.

    ``hidden``: (..., E); ``weight``: (V, E); ``targets``: hidden's leading
    shape, values in 1..V (any numeric dtype). Rows whose target equals
    ``ignore_index`` contribute nothing (and don't count toward the mean).
    ``chunk`` is the rows a tile (default: from the shapes,
    ``rows_per_tile``). Numerically equal to ``ClassNLL(LogSoftMax(logits),
    targets)`` without ever materialising (N, V) logits.

    ``row_weight`` (hidden's leading shape, float32) weighs each row's loss:
    the result is ``sum_r w_r l_r`` (over the count of valid rows with
    ``size_average``, not over the weights' sum), and its gradient with
    respect to the weights is the rows' losses ``l_r``. ``return_rows``
    (with a weight) also returns those ``l_r`` (hidden's leading shape,
    float32, 0 on ignored rows) as a second value, behind ``stop_gradient``:
    the gradient flows through the weighted sum alone.
    """
    e = hidden.shape[-1]
    h2 = hidden.reshape(-1, e)
    tgt = targets.reshape(-1).astype(jnp.int32)
    if ignore_index is not None:
        valid = tgt != int(ignore_index)
    else:
        valid = jnp.ones(tgt.shape, bool)
    rows = rows_per_tile(h2.shape[0], weight.shape[0], chunk)
    if row_weight is None:
        if return_rows:
            raise ValueError("the rows' losses come back with a row_weight")
        loss_sum = _lm_head_ce(h2, weight, bias, valid, tgt - 1, rows)
    else:
        loss_sum, per_row = _lm_head_ce_weighted(
            h2, weight, bias, row_weight.reshape(-1).astype(jnp.float32),
            valid, tgt - 1, rows)
    if size_average:
        loss_sum = loss_sum / jnp.maximum(
            jnp.sum(valid.astype(jnp.float32)), 1.0)
    if return_rows:
        return loss_sum, lax.stop_gradient(per_row).reshape(targets.shape)
    return loss_sum
