"""Fused int8-weight matmul Pallas kernel (round 5, VERDICT #5).

Round 4 measured int8 weight-only decode at only 1.19x fp32 while the
plain bf16 cast reached 1.69x: XLA lowers ``(q.astype(bf16) * scale) @ x``
as a dequantize kernel that WRITES the bf16 weight to HBM and a matmul
that reads it back — the int8 byte saving is spent twice. This kernel
keeps the weight int8 all the way into VMEM:

- grid (out_tiles, k_tiles), K innermost: the f32 output tile lives in
  VMEM across the K sweep (one revisit chain), int8 weight tiles stream
  HBM->VMEM at 1 byte/element;
- the tile dequantizes IN REGISTERS (int8 -> bf16 is exact for |q|<=127),
  feeds the MXU with bf16, accumulates f32;
- the per-output-channel scale multiplies ONCE after the K sweep
  (``(x @ q.T) * s == x @ (q*s).T`` exactly, since s is constant per
  output row) — so the kernel is also numerically tighter than
  dequantize-then-matmul.

Decode (B=1) at real model sizes is weight-READ-bound (PERF.md round-4
decode cost model), so halving resident bytes vs bf16 should approach 2x;
no benchmark cell measures it yet (ROADMAP W1).

Round 10 made the tiling FULL-COVERAGE: the grid rounds up and Pallas
masks the partial final output tile, so any (O, K%128==0) shape takes the
kernel at the largest tile under the waste bound — V=32000 moves from
125x 256-row tiles to 32x 1024-row tiles (2.4% tail padding), and
off-quantum vocabs like Qwen2's V=151936 keep the kernel (149 tiles,
0.4% padding) instead of losing it entirely.

``int8_matmul`` falls back to the XLA dequant path off-TPU, for big-M
prefill calls, or when K is off the 128-lane quantum; used by
``nn/quantized.py``'s Linear / LMHead / MultiHeadAttention twins.
"""

from __future__ import annotations

import functools
import warnings
from typing import Optional, Set, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Output-tile candidates, largest first: fewer grid steps = less per-step
# overhead (measured: at 368M the 256-row tiling paid ~1200 grid steps per
# decoded token and ran at half the weight-read roof). The weight block is
# (TO, K) int8 and must stay well under VMEM with double buffering.
_TO_CANDIDATES = (1024, 512, 256, 128)
_TILE_BYTES_CAP = 4 * 1024 * 1024
_M_PAD = 16  # bf16 sublane quantum

# Padded rows in the final partial tile are wasted weight-read bytes; cap
# them at 1/8 of the real output so an awkward O drops to a smaller tile
# instead of paying a mostly-empty large one (O=1100: a 1024-tile would
# read 86% garbage, the 128-tile reads 4.7%).
_WASTE_NUM, _WASTE_DEN = 1, 8


def _pick_to(out_dim: int, kdim: int) -> int:
    """Largest output tile whose int8 (TO, K) block fits the VMEM cap and
    whose final-partial-tile padding stays under the waste bound. O no
    longer has to divide the tile: the grid rounds up and Pallas masks
    the tail (OOB block reads are padded, OOB writes dropped — same
    semantics on Mosaic and in interpret mode). Returns 0 only when even
    the smallest tile would blow the VMEM cap (K > 32768)."""
    viable = [to for to in _TO_CANDIDATES if to * kdim <= _TILE_BYTES_CAP]
    if not viable:
        return 0
    for to in viable:
        waste = -out_dim % to
        if waste * _WASTE_DEN <= out_dim * _WASTE_NUM:
            return to
    # tiny / awkward O: every candidate over-pads, take the least-padded
    # (smallest) tile — still cheaper than the XLA dequant re-read
    return viable[-1]


def _kernel(x_ref, w_ref, s_ref, o_ref):
    # whole-K block per output tile: one dot, no output revisits (a
    # revisit-accumulate grid variant triggered a Mosaic compiler abort
    # when embedded in large decode programs on this toolchain)
    wt = w_ref[...].astype(jnp.bfloat16)            # int8 -> bf16 in-register
    acc = jax.lax.dot_general(
        x_ref[...], wt, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # (M, TO) f32 on the MXU
    o_ref[...] = acc * s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _int8_matmul_pallas(x2, w_q, scale_row, interpret=False):
    m, kdim = x2.shape
    out_dim = w_q.shape[0]
    to = _pick_to(out_dim, kdim)
    # ceil grid: the final output tile may be partial — Pallas pads OOB
    # reads of the weight/scale blocks and drops OOB writes of the
    # output block, so no in-kernel mask is needed
    no = (out_dim + to - 1) // to
    mp = max(_M_PAD, ((m + _M_PAD - 1) // _M_PAD) * _M_PAD)
    xp = jnp.zeros((mp, kdim), jnp.bfloat16).at[:m].set(
        x2.astype(jnp.bfloat16))
    call = pl.pallas_call(
        _kernel,
        grid=(no,),
        in_specs=[
            pl.BlockSpec((mp, kdim), lambda i: (0, 0)),
            pl.BlockSpec((to, kdim), lambda i: (i, 0)),
            pl.BlockSpec((1, to), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((mp, to), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((mp, out_dim), jnp.float32),
        interpret=interpret,
        name="int8_matmul",
    )
    out = call(xp, w_q, scale_row.reshape(1, out_dim).astype(jnp.float32))
    return out[:m]


# shapes already warned about: the fallback is per-call (traffic), the
# warning is once per distinct (K, O) — loud, not spammy
_FALLBACK_WARNED: Set[Tuple[int, int]] = set()


def _note_lost_kernel(kdim: int, out_dim: int) -> None:
    """A decode-shaped matmul whose REDUCTION dim is off the 128-lane
    quantum silently loses the fused kernel (the output dim no longer
    matters: the ceil grid covers any O — V=32000 runs 1024-row tiles,
    Qwen2's V=151936 keeps the kernel at 0.4% tail padding). Count the
    event (``bigdl_int8_fallbacks_total`` — once per eager call, once
    per TRACE under jit: the branch runs at trace time, so the counter
    counts shapes/compilations that lost the kernel, not per-step
    dispatches) and warn ONCE per shape, naming the shape and the
    quantum so the fix (pad K) is obvious from the log line."""
    from bigdl_tpu.telemetry import get_registry, instruments
    instruments(get_registry()).int8_fallbacks_total.inc()
    key = (kdim, out_dim)
    if key in _FALLBACK_WARNED:
        return
    _FALLBACK_WARNED.add(key)
    warnings.warn(
        f"int8_matmul: K={kdim} (out_dim={out_dim}) is off the 128-lane "
        f"quantum, so the fused int8 kernel is DISABLED for this shape "
        f"and the XLA dequantize path runs instead (weight bytes re-read "
        f"at bf16, ~2x the int8 floor). Pad the reduction dimension to a "
        f"multiple of 128 (e.g. pad the embed dim) to recover the "
        f"kernel.", RuntimeWarning, stacklevel=3)


def kernel_applicable(m: int, kdim: int, out_dim: int) -> bool:
    """Tiling gate: K must sit on the 128-lane quantum and the whole-K
    int8 weight block must fit VMEM at the smallest tile (K <= 32768).
    ANY output dim qualifies — the ceil grid masks the partial final
    tile. M is capped — for big-M prefill/batch the weight read
    amortizes and XLA's path is fine, while the kernel's fixed (M_pad, K)
    x-tile residency would bloat."""
    return (kdim % 128 == 0 and m <= 256
            and _pick_to(out_dim, kdim) > 0)


def int8_matmul(x: jax.Array, w_q: jax.Array, scale: jax.Array,
                bias: Optional[jax.Array] = None,
                compute_dtype=jnp.bfloat16) -> jax.Array:
    """``y = x @ (w_q * scale).T (+ bias)`` with w_q int8 (O, K) and a
    per-output-channel ``scale`` broadcastable to (O, 1). Dispatches to
    the fused Pallas kernel on TPU when the tiling divides; XLA
    dequant-then-matmul otherwise. Output in ``compute_dtype``."""
    lead = x.shape[:-1]
    kdim = x.shape[-1]
    out_dim = w_q.shape[0]
    x2 = x.reshape(-1, kdim)
    m = x2.shape[0]
    scale_row = jnp.asarray(scale).reshape(out_dim)
    interpret = jax.default_backend() != "tpu"
    if kernel_applicable(m, kdim, out_dim) and (not interpret or m <= 32):
        # off-TPU the interpreter is slow — only worth it at test sizes
        y = _int8_matmul_pallas(x2, w_q, scale_row, interpret=interpret)
        y = y.astype(compute_dtype)
    else:
        if m <= 256 and kdim % 128 != 0:
            # decode-shaped call that lost the kernel BECAUSE K is off
            # the lane quantum (a VMEM-capped K > 32768 is a deliberate
            # exclusion padding can't fix, and big-M calls amortize the
            # weight read anyway): loud once, counted per trace
            _note_lost_kernel(kdim, out_dim)
        w = w_q.astype(compute_dtype) * scale_row[:, None].astype(
            compute_dtype)
        y = jnp.matmul(x2.astype(compute_dtype), w.T)
    if bias is not None:
        # bias stays in ITS dtype (fp32 buffer): the add promotes the
        # output to fp32, matching the unfused twins' numerics — logits
        # argmax is sensitive to a bf16 downcast here
        y = y + bias
    return y.reshape(*lead, out_dim)
