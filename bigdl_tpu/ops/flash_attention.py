"""Flash attention as Pallas TPU kernels — forward AND backward.

New capability (no reference analogue — the reference's hottest hand-written
loops are im2col/col2im, ``nn/NNPrimitive.scala``; this is the TPU build's
equivalent "hand kernel" for its hottest new op). Three kernels:

- forward: online-softmax attention tiled for VMEM. grid = (batch*heads,
  query blocks); each program holds one query tile resident and streams
  key/value tiles for its (batch, head) row; running (acc, row_sum,
  row_max) carried in f32 on the VPU, the two matmuls per tile hit the
  MXU; causal masking skips fully-masked key tiles. Emits the row
  logsumexp (LSE) alongside the output — the residual the backward needs,
  and the statistic ring attention folds across devices.
- backward dQ: grid over query tiles; recomputes p = exp(logits - lse)
  per key tile (no O(S^2) materialisation) and accumulates
  dq += (p * (dO v^T - delta)) k * scale.
- backward dK/dV: grid over key tiles; streams query tiles, accumulating
  dv += p^T dO and dk += (p * (dO v^T - delta))^T q * scale. Causal runs
  start at the diagonal query tile.

The LSE output is a first-class differentiable output: its cotangent folds
into the delta term (d lse_i / d logits_ij = p_ij, so delta_i becomes
rowsum(dO_i * O_i) - g_lse_i). Ring attention exploits exactly this to
backprop through cross-device online-softmax combines.

On CPU the kernels run in Pallas interpret mode (tests); dispatch via
``use_flash`` selects the kernel on real TPU backends.
``BIGDL_TPU_FLASH_XLA_BWD=1`` falls back to the recompute-via-XLA backward
(A/B lever; it was the only backward before round 3).
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

_NEG = float(jnp.finfo(jnp.float32).min)


# ------------------------------------------------------------------ forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, l_ref, *, block_k: int, sk: int,
                causal: bool, scale: float, block_q: int):
    # q_ref: (1, BQ, D); k_ref/v_ref: (1, Sk_pad, D); o_ref: (1, BQ, D);
    # l_ref: (1, 1, BQ) row logsumexp of the scaled, masked logits. The
    # LSE rides a (BH, 1, S) array so its block's penultimate dim equals
    # the array dim — the real TPU lowering rejects (1, BQ) blocks over a
    # (BH, S) array (last-two-dims divisibility rule; interpret mode does
    # not enforce it, which is how this shipped unverified in round 2).
    j = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale                # (BQ, D)
    bq, d = q.shape
    nkb = k_ref.shape[1] // block_k

    q_pos = j * block_q + lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)

    def body(kb, carry):
        acc, rsum, rmax = carry
        kblk = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        vblk = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        logits = jnp.dot(q, kblk.T, preferred_element_type=jnp.float32)
        k_pos = kb * block_k + lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        valid = k_pos < sk
        if causal:
            valid = valid & (k_pos <= q_pos)
        logits = jnp.where(valid, logits, _NEG)
        blk_max = jnp.max(logits, axis=-1)
        new_max = jnp.maximum(rmax, blk_max)
        p = jnp.exp(logits - new_max[:, None])
        dead = new_max <= _NEG / 2                      # all-masked row so far
        p = jnp.where(dead[:, None], 0.0, p)
        corr = jnp.where(dead, 1.0, jnp.exp(rmax - new_max))
        new_sum = rsum * corr + jnp.sum(p, axis=-1)
        pv = jnp.dot(p, vblk, preferred_element_type=jnp.float32)
        new_acc = acc * corr[:, None] + pv
        return new_acc, new_sum, new_max

    if causal:
        # Key tiles strictly above the diagonal contribute nothing: the last
        # key position this query tile can see is its own last row.
        last_q = j * block_q + bq - 1
        nkb_eff = lax.min(nkb, lax.div(last_q, block_k) + 1)
    else:
        nkb_eff = nkb
    acc0 = jnp.zeros((bq, d), jnp.float32)
    sum0 = jnp.zeros((bq,), jnp.float32)
    max0 = jnp.full((bq,), _NEG, jnp.float32)
    acc, rsum, rmax = lax.fori_loop(0, nkb_eff, body, (acc0, sum0, max0))
    dead = rmax <= _NEG / 2
    rsum_safe = jnp.maximum(rsum, 1e-37)
    o_ref[0] = (acc / rsum_safe[:, None]).astype(o_ref.dtype)
    # Dead rows keep the finite _NEG sentinel (NOT -inf): downstream
    # logaddexp-style combines stay NaN-free on all-masked rows.
    l_ref[0, 0] = jnp.where(dead, _NEG, rmax + jnp.log(rsum_safe))


def _flash_fwd_lse(q, k, v, causal, scale, block_q, block_k, interpret):
    """Returns (o (B,Sq,N,D), lse (B,N,Sq) f32)."""
    b, sq, n, d = q.shape
    sk = k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    # BSND -> (B*N, S, D): one grid row per (batch, head).
    qt = q.transpose(0, 2, 1, 3).reshape(b * n, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * n, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * n, sk, d)
    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, pad_k), (0, 0)))
    sq_p, sk_p = qt.shape[1], kt.shape[1]

    grid = (b * n, sq_p // block_q)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, block_k=block_k, sk=sk,
                          causal=causal, scale=scale, block_q=block_q),
        out_shape=(jax.ShapeDtypeStruct((b * n, sq_p, d), q.dtype),
                   jax.ShapeDtypeStruct((b * n, 1, sq_p), jnp.float32)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, sk_p, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, sk_p, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=(pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
                   pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j))),
        interpret=interpret,
        name="flash_fwd",
    )(qt, kt, vt)
    out = out[:, :sq].reshape(b, n, sq, d).transpose(0, 2, 1, 3)
    lse = lse[:, 0, :sq].reshape(b, n, sq)
    return out, lse


# ----------------------------------------------------------------- backward

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, l_ref, d_ref, dq_ref, *,
                   block_k: int, sk: int, causal: bool, scale: float,
                   block_q: int):
    # Per query tile: stream key tiles, recompute p from the saved LSE.
    j = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)                        # (BQ, D)
    do = do_ref[0].astype(jnp.float32)                      # (BQ, D)
    lse = l_ref[0, 0]                                       # (BQ,)
    delta = d_ref[0, 0]                                     # (BQ,)
    bq, d = q.shape
    nkb = k_ref.shape[1] // block_k
    q_pos = j * block_q + lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)

    def body(kb, dq):
        kblk = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        vblk = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        logits = jnp.dot(q, kblk.T,
                         preferred_element_type=jnp.float32) * scale
        k_pos = kb * block_k + lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        valid = k_pos < sk
        if causal:
            valid = valid & (k_pos <= q_pos)
        # guard the exponent BEFORE exp (dead rows carry the _NEG sentinel;
        # the raw exponent would overflow), then mask
        expo = jnp.where(valid, logits - lse[:, None], 0.0)
        p = jnp.where(valid, jnp.exp(expo), 0.0)
        dp = jnp.dot(do, vblk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        return dq + jnp.dot(ds, kblk, preferred_element_type=jnp.float32)

    if causal:
        last_q = j * block_q + bq - 1
        nkb_eff = lax.min(nkb, lax.div(last_q, block_k) + 1)
    else:
        nkb_eff = nkb
    dq = lax.fori_loop(0, nkb_eff, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, l_ref, d_ref,
                    dk_ref, dv_ref, *, block_q: int, sk: int, sq: int,
                    causal: bool, scale: float, block_k: int):
    # Per key tile: stream query tiles. Padded query rows are masked out
    # explicitly (q_pos < sq): they carry the _NEG LSE sentinel, and
    # exp(logits - _NEG) = inf would otherwise poison dk/dv with inf*0=NaN
    # whenever seq is not a block_q multiple.
    jkb = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)                        # (BK, D)
    v = v_ref[0].astype(jnp.float32)
    bk, d = k.shape
    nqb = q_ref.shape[1] // block_q
    k_pos = jkb * block_k + lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)

    def body(qb, carry):
        dk, dv = carry
        qblk = q_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        doblk = do_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        lblk = l_ref[0, 0, pl.ds(qb * block_q, block_q)]    # (BQ,)
        dblk = d_ref[0, 0, pl.ds(qb * block_q, block_q)]    # (BQ,)
        logits = jnp.dot(qblk, k.T,
                         preferred_element_type=jnp.float32) * scale
        q_pos = qb * block_q + lax.broadcasted_iota(
            jnp.int32, (block_q, bk), 0)
        valid = (k_pos < sk) & (q_pos < sq)
        if causal:
            valid = valid & (k_pos <= q_pos)
        # guard the exponent BEFORE exp: a padded/dead row's _NEG sentinel
        # would overflow to inf and inf*0 -> NaN survives jnp.where
        expo = jnp.where(valid, logits - lblk[:, None], 0.0)
        p = jnp.where(valid, jnp.exp(expo), 0.0)            # (BQ, BK)
        dv = dv + jnp.dot(p.T, doblk, preferred_element_type=jnp.float32)
        dp = jnp.dot(doblk, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - dblk[:, None]) * scale
        dk = dk + jnp.dot(ds.T, qblk, preferred_element_type=jnp.float32)
        return dk, dv

    if causal:
        # Query tiles strictly before this key tile's first row see none of
        # its keys.
        first_qb = lax.div(jkb * block_k, block_q)
    else:
        first_qb = 0
    dk0 = jnp.zeros((bk, d), jnp.float32)
    dv0 = jnp.zeros((bk, d), jnp.float32)
    dk, dv = lax.fori_loop(first_qb, nqb, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, g_o, g_l, causal, scale, block_q, block_k,
               interpret):
    b, sq, n, d = q.shape
    sk = k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    qt = q.transpose(0, 2, 1, 3).reshape(b * n, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * n, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * n, sk, d)
    dot = g_o.transpose(0, 2, 1, 3).reshape(b * n, sq, d)
    ot = o.transpose(0, 2, 1, 3).reshape(b * n, sq, d)
    # lse/delta ride (BH, 1, S) arrays (see _fwd_kernel: the TPU lowering
    # rejects (1, BQ) blocks over a (BH, S) array).
    lt = lse.reshape(b * n, 1, sq)
    # delta_i = rowsum(dO_i * O_i) - g_lse_i (the LSE cotangent enters the
    # softmax jacobian exactly where the diagonal correction sits).
    delta = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32),
                    axis=-1)[:, None, :]
    if g_l is not None:
        delta = delta - g_l.reshape(b * n, 1, sq).astype(jnp.float32)

    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, pad_q), (0, 0)))
        dot = jnp.pad(dot, ((0, 0), (0, pad_q), (0, 0)))
        # pad value is irrelevant (padded query rows are masked by
        # q_pos < sq in both kernels); 0 keeps the exponent finite
        lt = jnp.pad(lt, ((0, 0), (0, 0), (0, pad_q)))
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, pad_q)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, pad_k), (0, 0)))
    sq_p, sk_p = qt.shape[1], kt.shape[1]

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_k=block_k, sk=sk,
                          causal=causal, scale=scale, block_q=block_q),
        out_shape=jax.ShapeDtypeStruct((b * n, sq_p, d), q.dtype),
        grid=(b * n, sq_p // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, sk_p, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, sk_p, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qt, kt, vt, dot, lt, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=block_q, sk=sk, sq=sq,
                          causal=causal, scale=scale, block_k=block_k),
        out_shape=(jax.ShapeDtypeStruct((b * n, sk_p, d), k.dtype),
                   jax.ShapeDtypeStruct((b * n, sk_p, d), v.dtype)),
        grid=(b * n, sk_p // block_k),
        in_specs=[
            pl.BlockSpec((1, sq_p, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, sq_p, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, sq_p), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, sq_p), lambda i, j: (i, 0, 0)),
        ],
        out_specs=(pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
                   pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0))),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qt, kt, vt, dot, lt, delta)

    dq = dq[:, :sq].reshape(b, n, sq, d).transpose(0, 2, 1, 3)
    dk = dk[:, :sk].reshape(b, n, sk, d).transpose(0, 2, 1, 3)
    dv = dv[:, :sk].reshape(b, n, sk, d).transpose(0, 2, 1, 3)
    return dq, dk, dv


# ------------------------------------------------------ differentiable core

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_lse(q, k, v, causal, scale, block_q, block_k, interpret):
    return _flash_fwd_lse(q, k, v, causal, scale, block_q, block_k,
                          interpret)


def _flash_lse_vjp_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    o, lse = _flash_lse(q, k, v, causal, scale, block_q, block_k, interpret)
    return (o, lse), (q, k, v, o, lse)


def _flash_lse_vjp_bwd(causal, scale, block_q, block_k, interpret, res, g):
    g_o, g_l = g
    q, k, v, o, lse = res
    if os.environ.get("BIGDL_TPU_FLASH_XLA_BWD"):
        # Pre-round-3 recompute path (A/B lever). Has no LSE cotangent
        # plumbing — valid only when nothing consumes lse downstream.
        from bigdl_tpu.ops.attention_core import blockwise_attention
        f = lambda q_, k_, v_: blockwise_attention(
            q_, k_, v_, causal=causal, scale=scale, block_size=block_k)
        _, vjp = jax.vjp(jax.checkpoint(f), q, k, v)
        return vjp(g_o)
    return _flash_bwd(q, k, v, o, lse, g_o, g_l, causal, scale,
                      block_q, block_k, interpret)


_flash_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


# ------------------------------------------------------------- public entry

# In-model on-chip default (PERF.md round-3 crossover table): 512/512 beat
# 256/256 and 128/128 at every measured LM config, op-level AND in-model.
_DEFAULT_BLOCK = 512


def _env_block(name: str, default: int) -> int:
    """On-chip block-size tuning without code edits
    (``BIGDL_TPU_FLASH_BLOCK_Q`` / ``BIGDL_TPU_FLASH_BLOCK_K``)."""
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Flash attention, shapes (B, S, N, D); differentiable (Pallas fwd+bwd)."""
    if scale is None:
        scale = 1.0 / float(q.shape[-1]) ** 0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if block_q is None:
        block_q = _env_block("BIGDL_TPU_FLASH_BLOCK_Q", _DEFAULT_BLOCK)
    if block_k is None:
        block_k = _env_block("BIGDL_TPU_FLASH_BLOCK_K", _DEFAULT_BLOCK)
    o, _ = _flash_lse(q, k, v, causal, scale, block_q, block_k, interpret)
    return o


def flash_attention_with_lse(
        q, k, v, causal: bool = False, scale: Optional[float] = None,
        block_q: Optional[int] = None, block_k: Optional[int] = None,
        interpret: Optional[bool] = None) -> Tuple[jax.Array, jax.Array]:
    """Flash attention returning ``(o (B,S,N,D), lse (B,N,S) f32)``.

    The LSE is differentiable (its cotangent folds into the softmax
    jacobian), which is what lets ring attention run this kernel per hop
    and still train: the cross-device combine consumes both outputs.
    All-masked rows carry the finite ``float32.min`` sentinel, not -inf.
    """
    if scale is None:
        scale = 1.0 / float(q.shape[-1]) ** 0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if block_q is None:
        block_q = _env_block("BIGDL_TPU_FLASH_BLOCK_Q", _DEFAULT_BLOCK)
    if block_k is None:
        block_k = _env_block("BIGDL_TPU_FLASH_BLOCK_K", _DEFAULT_BLOCK)
    return _flash_lse(q, k, v, causal, scale, block_q, block_k, interpret)


def use_flash(q, mask) -> bool:
    """Dispatch policy for MultiHeadAttention: Pallas kernel on real TPU for
    unmasked sequences (masked paths use the XLA cores which take an
    arbitrary additive bias).

    Gate encodes the measured in-model crossover (PERF.md round-3 table,
    real v5e): at seq 512 XLA's fused attention wins (the opaque
    pallas_call costs more in lost fusion + layout copies around it than
    online softmax saves there); from seq 1024 the kernel wins in-model —
    +22% tokens/s at 1024, +50% at 2048, +87% at 4096 (blocks 512/512).
    Op-level microbenchmarks showed flash ahead even at 512 — gate on
    IN-MODEL data, not op-level.
    """
    if os.environ.get("BIGDL_TPU_DISABLE_FLASH"):
        return False
    if mask is not None:
        return False
    if jax.default_backend() != "tpu":
        return False
    seq, d = q.shape[1], q.shape[-1]
    return seq >= 1024 and d % 64 == 0
