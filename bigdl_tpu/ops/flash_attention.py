"""Flash attention as Pallas TPU kernels — forward AND backward.

New capability (no reference analogue — the reference's hottest hand-written
loops are im2col/col2im, ``nn/NNPrimitive.scala``; this is the TPU build's
equivalent "hand kernel" for its hottest new op). Two kernels:

- forward: online-softmax attention tiled for VMEM. grid = (batch*heads,
  query blocks); each program holds one query tile resident and streams
  key/value tiles for its (batch, head) row; running (acc, row_sum,
  row_max) carried in f32 on the VPU, the two matmuls per tile hit the
  MXU; causal masking skips fully-masked key tiles. Emits the row
  logsumexp (LSE) alongside the output — the residual the backward needs,
  and the statistic ring attention folds across devices.
- backward, ONE call that returns dQ, dK and dV (PR 37; it keeps the name
  of the dK/dV kernel it grew from, ``flash_bwd_dkv``): grid = (batch*heads,
  key tiles), a row's key tiles in turn. A program holds its k and v tile
  and streams the query tiles it can see, on TRANSPOSED (key, query) tiles:
  it recomputes p = exp(logits - lse) (no O(S^2) materialisation) and
  ds = p * (dO v^T - delta) ONCE a tile and feeds all three gradients from
  them: dv += p^T dO and dk += ds^T q * scale into the program's own
  accumulators, and dq[query tile] += ds k * scale into a float32 buffer of
  the whole (batch, head) row that stays in VMEM over the row's key tiles
  (zeroed at the first, scaled, cast and written to HBM at the last). Causal
  runs start at the diagonal query tile. While dQ had a kernel of its own
  (grid over query tiles) every tile's logits, exp, mask and dO v^T were
  computed twice, 27-29% of the backward's MXU passes.

What the MXU is fed: every matmul takes its operands in the dtype the
caller's arrays have and accumulates in float32. The float32 intermediates
that feed a second matmul (p, ds) are cast to the operand dtype just before
it, as ``attention_core.dot_product_attention`` casts its weights; the
softmax statistics, the LSE, delta and the accumulators stay float32.
float32 callers get float32 operands. (On the v5e Mosaic rounds a float32
operand to bf16 inside the MXU, one pass either way: bf16 and up-cast tiles
measure the same and give the same bits; the operand dtype saves the casts,
not MXU passes. PERF.md section 6, PR 24.) No operand is transposed in
VMEM by this code: ``q k^T`` and ``dO v^T`` contract the head dim of both
operands (``dot_general``, NT), and the backward works on (key, query)
tiles so that dK's and dV's products are plain. dQ's is the one product
whose left operand is contracted over its sublanes (``ds`` as it lies is
(key, query)): Mosaic transposes the tile on the XLU, 64 transposes for a
(512, 512) tile, which the v5e's schedule hides under the tile's 128 MXU
passes (PERF.md section 6, PR 37).

Where the time went, and what the loop bodies do about it: a kernel's tile
loop runs within 5-20% of what its matmuls need with head 64 (half of the
128-wide MXU); the rest was around it. With square tiles on an unpadded
causal sequence the one tile the diagonal crosses is done as two
half-height strips, each against only the keys it can see (3/4 of its
products), and the tiles below it run unmasked in the one loop there is.
Every other call keeps one loop whose tiles are all masked (causal, or a
padded key tile) or all plain (``_masked``). The forward's LSE leaves
through ``_to_lanes``, not Mosaic's sublane-to-lane relayout. Measured
times and roofline shares: PERF.md section 5 (ledger, PR 24).

A sliding window (``window``, with ``causal``: query i sees the keys
``(i - window, i]``) is a static argument of the same two kernels. A call
that names none lowers to the code it lowered to before the band existed;
a call that names one bounds its one loop on BOTH sides: the forward starts
at the key tile that holds the oldest key the query tile's first row can
see, the backward ends at the query tile that holds the last query its last
key reaches. At 8,192 tokens and a window of 2,048 that is 5 of 16 key
tiles a query tile, for 14.7M query-key pairs where the full call holds
33.6M. What the band's two edges cost follows the call's shapes
(``_edge_strips``, PR 40). A square, unpadded call whose window is a whole
number of its tiles (and whose tile halves are whole lane groups) pays for
them only AT the edges: each edge then crosses ONE tile a program corner to
corner, the diagonal's and the one ``window / tile`` tiles from it, its
mirror image, and each runs as two half-height strips against only the keys
(queries, in the backward) it can see, 3/4 of the tile's products, as the
full call's diagonal does; the tiles between run UNMASKED in the one loop.
The forward takes both edge tiles after the loop, in one online-softmax
step a strip, and a query tile that reaches back to key 0 (no lower edge)
runs that edge's strips on tile 0 with every pair masked: a branch on the
program's index lengthened every program's schedule by more. The backward
branches: a key tile the last query ends has no far edge and skips its
strips. Every other banded call (an edge that cuts tiles at an angle,
a padded or oblong call) keeps one loop that masks every tile it meets (the
causal edge and the band's lower edge in one mask), code and bits. Banded
calls are named ``flash_band_fwd`` and ``flash_band_bwd_dkv``, so a trace
tells them from full calls of the same operand shape;
``bigdl_flash_attention_total{form=band|full|mla}`` counts each form once a
trace and ``bigdl_flash_band_edges_total{edges=strips|masked}`` how a banded
call's edges run. A window that reaches past the first key is no band: the
call is the full one, code and name.

The value head may differ from the query/key head (latent attention:
``q`` and ``k`` 192 wide, a 128-wide content part beside a 64-wide rotary
part, over a 128-wide ``v``): the kernels read each operand's own width,
the forward's accumulator, ``o``, ``dO`` and ``dV`` are as wide as ``v``
and ``dQ`` and ``dK`` as wide as ``q``. A call with equal sizes is the
code and the names it was; a call whose sizes differ is named
``flash_mla_fwd`` and ``flash_mla_bwd_dkv`` and counted ``form=mla``.
``k`` is ONE operand, the shared rotary key broadcast to the heads by the
caller: the two-product form (``qc kc^T + qr kr^T`` with the
rotary key's index map ignoring the head) feeds the MXU the same two
128-deep passes a 192-deep contraction takes; it would save the rotary
part's 32 copies in K (33.5 MB of 100 a layer at 8,192 tokens, written
once and read by both kernels) for a fourth operand in every kernel and a
``dkr`` summed over the heads (PERF.md section 6, PR 34).

The LSE output is a first-class differentiable output: its cotangent folds
into the delta term (d lse_i / d logits_ij = p_ij, so delta_i becomes
rowsum(dO_i * O_i) - g_lse_i). Ring attention exploits exactly this to
backprop through cross-device online-softmax combines.

On CPU the kernels run in Pallas interpret mode (tests); dispatch via
``use_flash`` selects the kernel on real TPU backends.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.remat import FLASH_OUT, keep
from bigdl_tpu.ops.scopes import under_scope

_NEG = float(jnp.finfo(jnp.float32).min)
_NT = (((1,), (1,)), ((), ()))        # a (M, K) x b (N, K) -> (M, N): b as it lies
_TN = (((0,), (0,)), ((), ()))        # a (K, M) x b (K, N) -> (M, N): a^T b


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    return lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)


def _dot_tn(a, b):
    return lax.dot_general(a, b, _TN, preferred_element_type=jnp.float32)


def _exact_scale(scale) -> bool:
    """A power of two scales an operand without rounding in any float
    dtype (1/sqrt(64) = 0.125: every head-64 model); any other scale goes
    on the float32 logits."""
    return scale > 0 and math.frexp(scale)[0] == 0.5


def _below(rows: int, cols: int, offset: int):
    """(rows, cols) mask, true where column <= row + offset."""
    return (lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
            <= lax.broadcasted_iota(jnp.int32, (rows, cols), 0) + offset)


def _visible(shape, key_axis: int, k0, q0, sk: int, causal: bool,
             window: Optional[int] = None):
    """Mask of a tile whose `key_axis` runs over the keys from position k0
    and whose other axis over the queries from q0: true where the key is
    real (not padding), under a causal mask not after the query, and under
    a band no more than ``window - 1`` before it."""
    k_pos = k0 + lax.broadcasted_iota(jnp.int32, shape, key_axis)
    valid = k_pos < sk
    if causal:
        valid = valid & (k_pos <= q0 + lax.broadcasted_iota(
            jnp.int32, shape, 1 - key_axis))
    if window is not None:
        valid = valid & (k_pos > q0 - window + lax.broadcasted_iota(
            jnp.int32, shape, 1 - key_axis))
    return valid


def _band_tiles(first, rows: int, block: int, n_blocks, window: int,
                behind: bool):
    """The tiles of the OTHER axis that a band lets the ``rows`` positions
    from ``first`` meet, as (first tile, one past the last). ``behind``:
    the positions are queries and the tiles hold keys, which reach from
    ``window - 1`` behind the first query up to the last query; else the
    positions are keys and the tiles hold queries, from the first key up to
    ``window - 1`` past the last one. Tiles outside are skipped whole, as
    the causal kernels skip those above the diagonal."""
    if behind:
        lo, hi = first - (window - 1), first + rows - 1
    else:
        lo, hi = first, first + rows - 1 + (window - 1)
    return (lax.div(lax.max(lo, 0), block),
            lax.min(n_blocks, lax.div(hi, block) + 1))


def _halved_diagonal(causal, sq, sk, block_q, block_k) -> bool:
    """Whether the one tile the causal diagonal crosses is done as two
    half-height strips, each against only the keys it can see (3/4 of the
    tile's products). Needs square tiles on the diagonal and halves that
    are whole lane groups; every other shape masks whole tiles."""
    return (causal and sq == sk and block_q == block_k
            and sk % block_k == 0 and block_k % 256 == 0)


def _edge_strips(causal, sq, sk, block_q, block_k,
                 window: Optional[int]) -> bool:
    """Whether the tiles an edge of the visible region crosses corner to
    corner run as two half-height strips and every tile between as it is,
    unmasked: the causal diagonal of a band-less call (``_halved_diagonal``)
    and, under a band, its lower edge as well, which needs a window of whole
    tiles: the edge then crosses ONE tile a program, ``window / block``
    tiles from the diagonal, as the diagonal's mirror image. A band whose
    edge cuts tiles at an angle masks every tile it meets."""
    return (_halved_diagonal(causal, sq, sk, block_q, block_k)
            and (window is None or window % block_k == 0))


def _masked(causal, sk, block_k) -> bool:
    """Whether a kernel off the halved-diagonal path masks its tiles: all of
    them or none, decided from the call, so that there is one tile loop. A
    second, unmasked loop for the tiles below the diagonal cost more in
    carry shuffling between the loops than the masks it saved (PR 24)."""
    return causal or sk % block_k != 0


def _to_lanes(col):
    """(N, 1) column -> (1, N) row. Mosaic's own sublane-to-lane relayout
    is 576 ``vperm`` for 512 values; selecting the diagonal of each
    (128, 128) block of the broadcast column and summing over its rows took
    the forward's epilogue from 1,282 to 739 bundles a program (PR 24)."""
    n = col.shape[0]
    c = min(n, 128)
    if n % c:
        return col[:, 0][None, :]
    eye = (lax.broadcasted_iota(jnp.int32, (c, c), 0)
           == lax.broadcasted_iota(jnp.int32, (c, c), 1))
    return jnp.concatenate(
        [jnp.sum(jnp.where(eye, col[i:i + c], 0.0), axis=0, keepdims=True)
         for i in range(0, n, c)], axis=1)


def _name(kernel: str, window: Optional[int], latent: bool = False) -> str:
    """The call's name in the HLO and the device trace: a call with a band
    is told from a full one of the same operand shape by it, and one whose
    value head differs from its query/key head (``latent``) from both."""
    if latent:
        return f"flash_mla_{kernel}"
    return f"flash_band_{kernel}" if window is not None else f"flash_{kernel}"


def _call_params(kernel: str, window: Optional[int], latent: bool,
                 vmem: Optional[int] = None, carried: bool = False) -> dict:
    """``pallas_call``'s name and what the call tells the compiler: its
    VMEM limit (``vmem`` None: the 16 MiB a call gets by default) and, where
    the grid's second axis carries an accumulator (``carried``), that a
    row's programs run in turn."""
    params = {"name": _name(kernel, window, latent)}
    if vmem is not None or carried:
        params["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=vmem, dimension_semantics=(
                ("parallel", "arbitrary") if carried else None))
    return params


def _lanes(width: int) -> int:
    """The lanes an operand's minor axis takes in VMEM: whole 128s (a head
    of 192 lies as 256)."""
    return -(-width // 128) * 128


def _bwd_vmem(sq_p: int, d: int, dv: int, block_q: int, block_k: int,
              itemsize: int) -> int:
    """The backward call's VMEM limit, from the call's own shapes: q and dO
    whole, the LSE and delta rows (a (1, S) float32 row lies on 8
    sublanes) and the k, v, dk, dv tiles, each twice (Mosaic double-buffers
    a call's operands); dQ's float32 accumulator and its block twice; the
    float32 dK and dV accumulators and four (BK, BQ) float32 intermediates
    of a tile (logits, p, dO v^T, ds); a quarter on top for what the
    compiler spills. At 8,192 tokens and 512-tiles a latent call (192 over
    128) reckons 35 MB (q and dO 12.6, dQ's accumulator 8.4 and block 8.4),
    a head-128 call 20 MB, the LM cell's 28 x 2,048 x 64 under 4 MB; a
    1024-tile adds 12.6 MB of intermediates. Never under the 16 MiB a call
    gets that names none."""
    ld, ldv = _lanes(d), _lanes(dv)
    need = (2 * sq_p * (ld + ldv) * itemsize + 2 * 2 * 8 * sq_p * 4
            + 4 * block_k * (ld + ldv) * itemsize
            + sq_p * ld * (4 + 2 * itemsize)
            + 2 * block_k * (ld + ldv) * 4 + 4 * block_k * block_q * 4)
    return max(16 << 20, need * 5 // 4)


def _fwd_vmem(sk_p: int, d: int, dv: int, block_q: int, block_k: int,
              itemsize: int) -> Optional[int]:
    """The forward call's VMEM limit, from the call's own shapes, or None
    where the 16 MiB a call gets by default hold it (every call of up to
    8,192 keys at head 128 in bf16, which so lowers to what it lowered to
    before this rule). K and V lie whole in VMEM and the q and o tiles
    beside them, each twice (Mosaic double-buffers a call's operands), with
    two (BQ, BK) float32 intermediates and three (BQ, dv) float32
    accumulators; a quarter on top for what the compiler spills. At 16,384
    keys of head 128 in bf16 K and V alone are the 16 MiB (the v5e's
    compiler refused the call while it named no limit) and the call
    reckons 25 MB, at 32,768 keys 46 MB; the chip has 128 MiB."""
    ld, ldv = _lanes(d), _lanes(dv)
    need = (2 * (sk_p + block_q) * (ld + ldv) * itemsize
            + 2 * block_q * block_k * 4 + 3 * block_q * ldv * 4)
    return None if need <= 16 << 20 else min(_VMEM_MOST, need * 5 // 4)


# ------------------------------------------------------------------ forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, l_ref, *, block_k: int, sk: int,
                causal: bool, scale: float, block_q: int, diagonal: bool,
                window: Optional[int] = None):
    # q_ref: (1, BQ, D); k_ref: (1, Sk_pad, D); v_ref: (1, Sk_pad, Dv);
    # o_ref: (1, BQ, Dv), Dv = D but in a latent call;
    # l_ref: (1, 1, BQ) row logsumexp of the scaled, masked logits. The
    # LSE rides a (BH, 1, S) array so its block's penultimate dim equals
    # the array dim — the real TPU lowering rejects (1, BQ) blocks over a
    # (BH, S) array (last-two-dims divisibility rule; interpret mode does
    # not enforce it, which is how this shipped unverified in round 2).
    j = pl.program_id(1)
    q = q_ref[0]                                            # (BQ, D)
    bq = q.shape[0]
    nkb = k_ref.shape[1] // block_k
    prescaled = _exact_scale(scale)
    if prescaled:
        q = q * jnp.asarray(scale, q.dtype)

    def update(carry, rows, *pieces):
        # One online-softmax step of the query rows `rows` against the keys
        # of every piece (k0, width, valid): [k0, k0 + width) under the
        # mask `valid`, one running maximum and one rescaling for them all.
        # Without a band key 0 is visible to every row and is in the first
        # tile a row meets, so no row is all-masked when its running
        # maximum is first used. Under a band that masks every tile a
        # row's first tiles can lie wholly below its window: they leave
        # p = 1 against a running maximum of _NEG, and the first tile that
        # holds a visible key (the row's own, at the latest) wipes that
        # with corr = exp(_NEG - max) = 0. Where the band's edges run as
        # strips the tile's LAST row sees nothing of the lower-edge tile
        # (the columns past its own: there is none); that tile shares its
        # step with the diagonal's, which holds the row's own key.
        acc, rsum, rmax = (x[rows] for x in carry)
        new_max, logits, values = rmax, [], []
        for k0, width, valid in pieces:
            kblk = k_ref[0, pl.ds(k0, width), :]
            values.append(v_ref[0, pl.ds(k0, width), :])
            logit = _dot_nt(q[rows], kblk)                  # f32
            if not prescaled:
                logit = logit * scale
            if valid is not None:
                logit = jnp.where(valid, logit, _NEG)
            new_max = jnp.maximum(new_max,
                                  jnp.max(logit, axis=-1, keepdims=True))
            logits.append(logit)
        ps = [jnp.exp(logit - new_max) for logit in logits]
        corr = jnp.exp(rmax - new_max)
        new_sum = rsum * corr
        for p in ps:
            new_sum = new_sum + jnp.sum(p, axis=-1, keepdims=True)
        new_acc = acc * corr
        for p, vblk in zip(ps, values):
            new_acc = new_acc + _dot(p.astype(vblk.dtype), vblk)
        return new_acc, new_sum, new_max

    def tile(kb, carry, masked):
        valid = _visible((bq, block_k), 1, kb * block_k, j * block_q, sk,
                         causal, window) if masked else None
        return update(carry, slice(None), (kb * block_k, block_k, valid))

    def strips(carry, *tiles):
        # The key tiles an edge crosses corner to corner, each (kb,
        # mirrored, live), as two half-height strips of the query rows,
        # each against only the keys it can see and in ONE online-softmax
        # step for all its tiles. On the diagonal a row sees the columns up
        # to its own: the upper rows the first half of the keys, the lower
        # rows all. `mirrored`, the band's lower edge: a row sees the
        # columns PAST its own, the upper rows all the keys, the lower
        # rows the second half. `live` (None: it is) masks a tile whole.
        h = bq // 2

        def pieces(lower):
            for kb, mirrored, live in tiles:
                k0 = kb * block_k
                if mirrored:
                    k0, width, valid = (
                        (k0 + h, h, ~_below(h, h, 0)) if lower else
                        (k0, block_k, ~_below(h, block_k, 0)))
                else:
                    k0, width, valid = (
                        (k0, block_k, _below(h, block_k, h)) if lower else
                        (k0, h, _below(h, h, 0)))
                yield k0, width, valid if live is None else valid & live

        upper = update(carry, slice(0, h), *pieces(False))
        lower = update(carry, slice(h, bq), *pieces(True))
        return tuple(jnp.concatenate(x) for x in zip(upper, lower))

    carry = (jnp.zeros((bq, v_ref.shape[-1]), jnp.float32),
             jnp.zeros((bq, 1), jnp.float32),
             jnp.full((bq, 1), _NEG, jnp.float32))
    if diagonal:
        # key tiles [first, j) lie wholly below the diagonal and, under a
        # band, wholly inside it (_edge_strips); tile j is on the diagonal
        reach = None if window is None else window // block_k
        first = 0 if reach is None else lax.max(j - reach + 1, 0)
        carry = lax.fori_loop(first, j, functools.partial(tile, masked=False),
                              carry)
        edges = ((j, False, None),)
        if reach is not None:
            # the band's lower edge crosses tile j - reach and shares the
            # diagonal's step. The first `reach` query tiles reach back to
            # key 0 and have none: their strips read tile 0 with every
            # pair masked, p = 0 against the diagonal's maximum. A branch
            # on j instead costs every program more than those dead strips
            # cost the few: its schedule is 4% longer in the loop and 9%
            # around it (PERF.md section 6, PR 40)
            edges = ((lax.max(j - reach, 0), True, j >= reach),) + edges
        acc, rsum, rmax = strips(carry, *edges)
    elif window is not None:
        # A band whose edge cuts tiles at an angle: the key tiles from the
        # one that holds the first row's oldest visible key to the one on
        # the diagonal, every one masked.
        first, last = _band_tiles(j * block_q, bq, block_k, nkb, window,
                                  behind=True)
        acc, rsum, rmax = lax.fori_loop(
            first, last, functools.partial(tile, masked=True), carry)
    else:
        # One loop, masked or not as a whole (_masked). Key tiles strictly
        # above the diagonal contribute nothing: the last key this query
        # tile can see is its own last row.
        if causal:
            nkb = lax.min(nkb, lax.div(j * block_q + bq - 1, block_k) + 1)
        acc, rsum, rmax = lax.fori_loop(
            0, nkb, functools.partial(
                tile, masked=_masked(causal, sk, block_k)), carry)
    dead = rmax <= _NEG / 2
    rsum_safe = jnp.maximum(rsum, 1e-37)
    o_ref[0] = (acc / rsum_safe).astype(o_ref.dtype)
    # Dead rows keep the finite _NEG sentinel (NOT -inf): downstream
    # logaddexp-style combines stay NaN-free on all-masked rows.
    l_ref[0] = _to_lanes(jnp.where(dead, _NEG, rmax + jnp.log(rsum_safe)))


def _flash_fwd_lse(q, k, v, causal, scale, block_q, block_k, interpret,
                   window=None):
    """Returns (o (B,Sq,N,Dv), lse (B,N,Sq) f32)."""
    b, sq, n, d = q.shape
    sk, dv = k.shape[1], v.shape[-1]
    block_q, block_k = _fwd_tiles(q, k, v, block_q, block_k, causal, window)
    # BSND -> (B*N, S, D): one grid row per (batch, head).
    qt = q.transpose(0, 2, 1, 3).reshape(b * n, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * n, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * n, sk, dv)
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
                          causal=causal, scale=scale, block_q=block_q,
                          diagonal=_edge_strips(causal, sq, sk, block_q,
                                                block_k, window),
                          window=window),
        out_shape=(jax.ShapeDtypeStruct((b * n, sq_p, dv), q.dtype),
                   jax.ShapeDtypeStruct((b * n, 1, sq_p), jnp.float32)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, sk_p, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, sk_p, dv), lambda i, j: (i, 0, 0)),
        ],
        out_specs=(pl.BlockSpec((1, block_q, dv), lambda i, j: (i, j, 0)),
                   pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j))),
        interpret=interpret,
        # a 192-wide operand lies in VMEM as 256 lanes, so at 8,192 tokens a
        # latent call's whole K and V are 12 MB double-buffered, which with
        # the tiles passes the default limit (the v5e's compiler refuses
        # it, PR 34); a call with equal sizes names no limit where the
        # default holds it, and from 16,384 keys of head 128 its own
        **_call_params("fwd", window, dv != d,
                       _LATENT_VMEM if dv != d else _fwd_vmem(
                           sk_p, d, dv, block_q, block_k,
                           q.dtype.itemsize)),
    )(qt, kt, vt)
    out = out[:, :sq].reshape(b, n, sq, dv).transpose(0, 2, 1, 3)
    lse = lse[:, 0, :sq].reshape(b, n, sq)
    return out, lse


# ----------------------------------------------------------------- backward

def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, l_ref, d_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, *, block_q: int, sk: int,
                causal: bool, scale: float, block_k: int,
                diagonal: bool, window: Optional[int] = None):
    # Per key tile: stream query tiles, everything TRANSPOSED: the logits
    # tile is (BK, BQ), so four of the five matmuls take their operands as
    # they lie (k q^T and v dO^T contract the shared head dim, p^T dO and
    # ds^T q are plain) and the LSE and delta rows broadcast from the lanes
    # they are stored in. Padded query rows need no mask: _flash_bwd pads q,
    # dO, the LSE and delta with zeros, so there p = exp(0 - 0) = 1 meets
    # dO = 0 in dV and ds = 1 * (0 - 0) in dK and dQ.
    jkb = pl.program_id(1)
    k = k_ref[0]                                            # (BK, D)
    v = v_ref[0]                                            # (BK, Dv)
    bk, d = k.shape
    nqb = q_ref.shape[1] // block_q
    prescaled = _exact_scale(scale)
    ks = k * jnp.asarray(scale, k.dtype) if prescaled else k

    @pl.when(jkb == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def part(keys, q0, width, valid):
        # (sum(ds^T q), sum(p^T dO)) of the key rows `keys` over the
        # queries [q0, q0 + width), whose dQ rows take ds k on the way
        qblk = q_ref[0, pl.ds(q0, width), :]
        doblk = do_ref[0, pl.ds(q0, width), :]
        lrow = l_ref[0, :, pl.ds(q0, width)]                # (1, width) f32
        drow = d_ref[0, :, pl.ds(q0, width)]
        logits = _dot_nt(ks[keys], qblk)                    # f32
        if not prescaled:
            logits = logits * scale
        if valid is None:
            p = jnp.exp(logits - lrow)
        else:
            # guard the exponent BEFORE exp: a dead row's _NEG sentinel
            # would overflow to inf and inf*0 -> NaN survives jnp.where
            expo = jnp.where(valid, logits - lrow, 0.0)
            p = jnp.where(valid, jnp.exp(expo), 0.0)
        ds = (p * (_dot_nt(v[keys], doblk) - drow)).astype(qblk.dtype)
        dq_acc[pl.ds(q0, width), :] += _dot_tn(ds, k[keys])
        return _dot(ds, qblk), _dot(p.astype(doblk.dtype), doblk)

    def tile(qb, carry, masked):
        valid = _visible((bk, block_q), 0, jkb * block_k, qb * block_q, sk,
                         causal, window) if masked else None
        dk, dv = part(slice(None), qb * block_q, block_q, valid)
        return carry[0] + dk, carry[1] + dv

    zeros = (jnp.zeros((bk, d), jnp.float32),
             jnp.zeros((bk, v.shape[-1]), jnp.float32))
    def strips(qb, mirrored):
        # The query tile qb, which an edge crosses corner to corner, as two
        # half-height strips of the keys, each against only the queries
        # that see it. On the diagonal a key is seen from its own position
        # on: the first half of the keys by the whole tile, the second by
        # its second half. `mirrored`, the band's far edge: a key is seen
        # by the queries BEFORE its own position in the tile, the first
        # half of the keys by the tile's first half, the second by all.
        h = bk // 2
        if mirrored:
            upper = part(slice(0, h), qb * block_q, h, _below(h, h, -1))
            lower = part(slice(h, bk), qb * block_q, block_q,
                         _below(h, block_q, h - 1))
        else:
            upper = part(slice(0, h), qb * block_q, block_q,
                         ~_below(h, block_q, -1))
            lower = part(slice(h, bk), qb * block_q + h, h,
                         ~_below(h, h, -1))
        return tuple(jnp.concatenate(x) for x in zip(upper, lower))

    if diagonal:
        # query tile jkb is on the diagonal; the query tiles after it see
        # every key and, under a band, up to the one on its far edge are
        # seen by every key (_edge_strips)
        carry = strips(jkb, mirrored=False)
        last = nqb
        if window is not None:
            reach = window // block_q
            last = lax.min(jkb + reach, nqb)
        dk, dv = lax.fori_loop(jkb + 1, last,
                               functools.partial(tile, masked=False), carry)
        if window is not None:
            # the band's far edge crosses query tile jkb + reach; the last
            # `reach` key tiles reach the last query and have none
            dk, dv = lax.cond(
                jkb + reach < nqb,
                lambda c: tuple(a + b for a, b in zip(
                    c, strips(jkb + reach, mirrored=True))),
                lambda c: c, (dk, dv))
    elif window is not None:
        # A band whose edge cuts tiles at an angle: from the query tile
        # that holds this key tile's first row to the one that holds the
        # last query its last key reaches, every one masked.
        first, last = _band_tiles(jkb * block_k, bk, block_q, nqb, window,
                                  behind=False)
        dk, dv = lax.fori_loop(first, last,
                               functools.partial(tile, masked=True), zeros)
    else:
        # Causal: query tiles strictly before this key tile's first row see
        # none of its keys.
        first_qb = lax.div(jkb * block_k, block_q) if causal else 0
        dk, dv = lax.fori_loop(first_qb, nqb, functools.partial(
            tile, masked=_masked(causal, sk, block_k)), zeros)
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(jkb == pl.num_programs(1) - 1)
    def _():
        # dq = scale * sum(ds k): once on the float32 accumulator
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _flash_bwd(q, k, v, o, lse, g_o, g_l, causal, scale, block_q, block_k,
               interpret, window=None):
    b, sq, n, d = q.shape
    sk, d_v = k.shape[1], v.shape[-1]
    default = _bwd_block(sq, sk, d, d_v, q.dtype.itemsize, causal, window)
    block_q = min(block_q or default, sq)
    block_k = min(block_k or default, sk)
    qt = q.transpose(0, 2, 1, 3).reshape(b * n, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * n, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * n, sk, d_v)
    dot = g_o.transpose(0, 2, 1, 3).reshape(b * n, sq, d_v)
    ot = o.transpose(0, 2, 1, 3).reshape(b * n, sq, d_v)
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
        # zeros, so that padded query rows add nothing to dK and dV
        # (p = exp(0 - 0) = 1 times dO = 0 and ds = 0): see _bwd_kernel
        lt = jnp.pad(lt, ((0, 0), (0, 0), (0, pad_q)))
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, pad_q)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, pad_k), (0, 0)))
    sq_p, sk_p = qt.shape[1], kt.shape[1]
    diagonal = _edge_strips(causal, sq, sk, block_q, block_k, window)

    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, block_q=block_q, sk=sk,
                          causal=causal, scale=scale, block_k=block_k,
                          diagonal=diagonal, window=window),
        out_shape=(jax.ShapeDtypeStruct((b * n, sq_p, d), q.dtype),
                   jax.ShapeDtypeStruct((b * n, sk_p, d), k.dtype),
                   jax.ShapeDtypeStruct((b * n, sk_p, d_v), v.dtype)),
        grid=(b * n, sk_p // block_k),
        in_specs=[
            pl.BlockSpec((1, sq_p, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d_v), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, sq_p, d_v), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, sq_p), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, sq_p), lambda i, j: (i, 0, 0)),
        ],
        # dQ's block is the whole row for every key tile of it: it stays in
        # VMEM while they run and is written back once, when the row ends
        out_specs=(pl.BlockSpec((1, sq_p, d), lambda i, j: (i, 0, 0)),
                   pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
                   pl.BlockSpec((1, block_k, d_v), lambda i, j: (i, j, 0))),
        scratch_shapes=[pltpu.VMEM((sq_p, d), jnp.float32)],
        interpret=interpret,
        **_call_params("bwd_dkv", window, d_v != d,
                       min(_VMEM_MOST, _bwd_vmem(
                           sq_p, d, d_v, block_q, block_k,
                           q.dtype.itemsize)), carried=True),
    )(qt, kt, vt, dot, lt, delta)

    dq = dq[:, :sq].reshape(b, n, sq, d).transpose(0, 2, 1, 3)
    dk = dk[:, :sk].reshape(b, n, sk, d).transpose(0, 2, 1, 3)
    dv = dv[:, :sk].reshape(b, n, sk, d_v).transpose(0, 2, 1, 3)
    return dq, dk, dv


# ------------------------------------------------------ differentiable core

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_lse(q, k, v, causal, scale, block_q, block_k, interpret, window):
    return _flash_fwd_lse(q, k, v, causal, scale, block_q, block_k,
                          interpret, window)


def _flash_lse_vjp_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                       window):
    o, lse = _flash_lse(q, k, v, causal, scale, block_q, block_k, interpret,
                        window)
    # kept across a block's rematerialisation (ops.remat), HERE because
    # both are residuals of this rule as well as outputs: a tag on the
    # caller's ``o`` alone leaves ``lse`` to be recomputed, which runs the
    # forward kernel a second time
    o, lse = keep(o, FLASH_OUT), keep(lse, FLASH_OUT)
    return (o, lse), (q, k, v, o, lse)


@under_scope("attn_core")
def _flash_lse_vjp_bwd(causal, scale, block_q, block_k, interpret, window,
                       res, g):
    g_o, g_l = g
    q, k, v, o, lse = res
    return _flash_bwd(q, k, v, o, lse, g_o, g_l, causal, scale,
                      block_q, block_k, interpret, window)


_flash_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


# ------------------------------------------------------------- public entry

# The square tile, when the caller names none. Measured op-level on the v5e,
# bf16, at head 64 and 128, sequences 1024 to 8192, causal and not (PERF.md
# section 6, PR 24). The backward's tile: _bwd_block.
# The forward gains 8-32% at 1024 where no tile of the call is masked as a
# whole (half as many programs share its fixed work), loses 11% where every
# tile is (a padded causal sequence), and at 1024 its two (BQ, BK) float32
# intermediates take 8 MiB of the 16 MiB of VMEM a call gets, beside K and V
# which lie there whole: see _fwd_block.
_BIG_BLOCK = 1024
_BLOCK = 512
_VMEM_BUDGET = 15 << 20
_VMEM_MOST = 100 << 20      # what the backward may name of the chip's 128 MiB
_LATENT_VMEM = 32 << 20     # a latent forward's limit: _flash_fwd_lse


def _fwd_block(sq: int, sk: int, d: int, dv: int, itemsize: int) -> int:
    """The forward's default tile, from what the call can see: 1024 where
    the sequence is a whole number of such tiles (so the causal diagonal is
    halved and nothing else is masked) and the call's VMEM stays under the
    budget; else 512, the parent's. The estimate, for a query/key head of
    ``d`` and a value head of ``dv``: K (``d`` wide) and V (``dv``) whole
    and the q (``d``) and o (``dv``) tiles, each twice (Mosaic
    double-buffers its operands), two (BQ, BK) float32 intermediates and
    three (BQ, dv) float32 accumulators. Every shape it admits compiled for
    the v5e (bf16 and float32, head 64 to 256, 1024 to 16384 keys, causal
    and not), and it refuses every one that did not at 1024 (bf16: head 128
    from 8192 keys, head 256 from 2048; float32: head 128 from 4096) with a
    few that would have (PERF.md section 6, PR 24). A call whose two head
    sizes differ takes 512 whatever its length: none has been compiled at
    1024 on the chip, and a head of 192 lies in VMEM as 256 lanes, which
    the estimate does not know (a head that lies so, 256, failed at 1024
    from 2,048 keys)."""
    big = _BIG_BLOCK
    vmem = (2 * (sk + big) * (d + dv) * itemsize + 2 * big * big * 4
            + 3 * big * dv * 4)
    if d == dv and sq == sk and sk % big == 0 and vmem <= _VMEM_BUDGET:
        return big
    return _BLOCK


def _fwd_tiles(q, k, v, block_q, block_k, causal,
               window) -> Tuple[int, int]:
    """The forward's (query, key) tile: the caller's, else the default of
    the call's form, and never longer than the sequence. Without a band
    the default is ``_fwd_block``'s. Under one it is 1024 where the
    backward's is (``_bwd_block``: the edges run as strips at that size and
    the window is at least two such tiles) and the limit the call would
    name (``_fwd_vmem``) is one it may; else 512. ``_fwd_block``'s budget is
    not asked: it is the 16 MiB of a call that names no limit, and refuses
    1024 at head 128 from 8,192 keys, which is where the cells' bands are.
    Measured on the v5e, bf16, ms a call (PERF.md section 6, PR 40; the
    all-masked loop at 512 first): 28 x 16,384 x 128, window 4,096: 8.58,
    8.26 with the edges as strips at 512, 7.94 at 1024; 32 x 8,192 x 128,
    window 2,048: 3.01, 2.89, 2.74. The tile loop's schedule is no shorter
    at 1024 (5,594 bundles for four times 1,393); half as many programs
    pay for the strips and the epilogue."""
    sq, sk = q.shape[1], k.shape[1]
    d, dv, itemsize = q.shape[-1], v.shape[-1], q.dtype.itemsize
    big = _BIG_BLOCK
    if window is None:
        default = _fwd_block(sq, sk, d, dv, itemsize)
    elif (_edge_strips(causal, sq, sk, big, big, window)
          and window >= 2 * big
          and (_fwd_vmem(sk, d, dv, big, big, itemsize) or 0) < _VMEM_MOST):
        default = big
    else:
        default = _BLOCK
    return min(block_q or default, sq), min(block_k or default, sk)


def _bwd_block(sq: int, sk: int, d: int, dv: int, itemsize: int,
               causal: bool, window: Optional[int]) -> int:
    """The backward's default tile, from what the call can see: 1024 x 1024
    where the edges run as strips at that size (``_edge_strips``: a square,
    unpadded causal call of whole 1024-tiles and, under a band, a window of
    at least two of them) and the call's VMEM (``_bwd_vmem``) stays under
    what it may name; else 512 x 512. Where the edges are strips the tiles
    between them run unmasked, and a tile of four times the pairs shares
    its loads of k, v and the accumulators' carry over four times the
    products. Measured on the v5e, bf16, ms a call (PERF.md section 6, PR
    37): 13.50 against 14.70 at 512 for a latent call of 32 x 8,192 (192
    over 128), 8.32 against 9.18 at head 128, 0.711 against 0.721 at 28 x
    2,048 x 64; the schedule of the tile loop said so before the chip did
    (3,698 bundles a (512, 512) of pairs against 4,135 latent, 2,363
    against 2,532 at head 128). Under a band with its edges as strips (PR
    40; the all-masked loop at 512 in the same call first): 28 x 16,384 x
    128, window 4,096: 16.26, 15.14 at 512, 14.07 at 1024; 32 x 8,192 x
    128, window 2,048: 5.35, 4.90, 4.65. A 1024-tile computes half a tile
    more past the band than two 512s do (4.5 tiles for 4 of window and 2.5
    for 2, against 8.5 / 8 and 4.5 / 4) and still wins both; a window of
    ONE 1024-tile (1.5 tiles computed for 1) is not measured and keeps 512.
    Oblong tiles lose the strips and mask every tile (1024 x 512: +5% at
    head 128, +10% under a band, -2% latent; 512 x 1024 +7 / +11 / +1%),
    smaller ones pay the loop's fixed work more often (256 x 512 +8 to
    +23%, 256 x 256 +39 to +62%)."""
    big = _BIG_BLOCK
    if (_edge_strips(causal, sq, sk, big, big, window)
            and (window is None or window >= 2 * big)
            and _bwd_vmem(sq, d, dv, big, big, itemsize) <= _VMEM_MOST):
        return big
    return _BLOCK


def _band_of(window: Optional[int], causal: bool, q, k, v, block_q,
             block_k) -> Optional[int]:
    """The band the kernels are told of, counted by form and, where there
    is one, by how its edges run. A window that reaches past the first key
    cuts nothing: the call is then the full one, code and name. A latent
    call (the value head differs from the query/key head) has its own names
    and form and takes no band (a trace could not tell such a call, and
    nothing makes one)."""
    sk, latent = k.shape[1], v.shape[-1] != q.shape[-1]
    if window is not None:
        if not causal:
            raise ValueError("window (a banded causal mask) needs "
                             "causal=True")
        if window < 1:
            raise ValueError("window must be >= 1")
        if window >= sk:
            window = None
    if latent and window is not None:
        raise ValueError("a sliding window with a value head that differs "
                         "from the query/key head is not supported")
    from bigdl_tpu.telemetry import get_registry, instruments
    # trace-time count, as bigdl_ssd_scan_total: which form a compiled
    # program holds, not per-step traffic
    ins = instruments(get_registry())
    ins.flash_attention_total.labels(
        form="mla" if latent else "full" if window is None else "band").inc()
    if window is not None:
        # by the forward's tiles; the backward's default follows them
        # (both take 1024 only where the edges are strips at it)
        strips = _edge_strips(causal, q.shape[1], sk, *_fwd_tiles(
            q, k, v, block_q, block_k, causal, window), window)
        ins.flash_band_edges_total.labels(
            edges="strips" if strips else "masked").inc()
    return window


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None) -> jax.Array:
    """Flash attention, q and k (B, S, N, D), v and the result (B, S, N, Dv);
    differentiable (Pallas fwd+bwd). ``scale`` defaults to ``1/sqrt(D)``,
    the query/key head's. Where ``Dv`` is not ``D`` (latent attention: a
    192-wide q/k of a content and a rotary part over a 128-wide v) the same
    two kernels run under the names ``flash_mla_*``, with nothing padded.

    ``window`` (with ``causal``): query i sees the keys ``(i - window, i]``,
    the Mistral convention. The kernels skip the tiles that lie wholly
    below the band and mask its lower edge: in the one tile it crosses
    where the window is a whole number of tiles, else in every tile."""
    if scale is None:
        scale = 1.0 / float(q.shape[-1]) ** 0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    window = _band_of(window, causal, q, k, v, block_q, block_k)
    o, _ = _flash_lse(q, k, v, causal, scale, block_q, block_k, interpret,
                      window)
    return o


def flash_attention_with_lse(
        q, k, v, causal: bool = False, scale: Optional[float] = None,
        block_q: Optional[int] = None, block_k: Optional[int] = None,
        interpret: Optional[bool] = None, window: Optional[int] = None
        ) -> Tuple[jax.Array, jax.Array]:
    """Flash attention returning ``(o (B,S,N,Dv), lse (B,N,S) f32)``.

    The LSE is differentiable (its cotangent folds into the softmax
    jacobian), which is what lets ring attention run this kernel per hop
    and still train: the cross-device combine consumes both outputs.
    All-masked rows carry the finite ``float32.min`` sentinel, not -inf.
    """
    if scale is None:
        scale = 1.0 / float(q.shape[-1]) ** 0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    window = _band_of(window, causal, q, k, v, block_q, block_k)
    return _flash_lse(q, k, v, causal, scale, block_q, block_k, interpret,
                      window)


def use_flash(q, mask) -> bool:
    """Dispatch policy for MultiHeadAttention: Pallas kernel on real TPU for
    sequences without an arbitrary mask (those use the XLA cores, which take
    any additive bias). A causal mask and a sliding window are no ``mask``
    here: both are arguments of the kernels. The head size tested is the
    QUERY/KEY head's (``q``'s last axis, the one ``q k^T`` contracts); the
    value head may differ and is not looked at (a latent call's 192 / 128
    passes on its 192).

    The gate is the in-model crossover measured in round 3 on a v5e, with
    the kernels then fed float32 (ROADMAP S5 keeps those numbers): at seq
    512 XLA's fused attention won (the opaque pallas_call costs more in
    lost fusion + layout copies around it than online softmax saves
    there); from seq 1024 the kernel won in-model. Op-level the kernel was
    ahead even at 512 — gate on IN-MODEL data, not op-level. Not re-measured
    since the kernels take bf16 operands (PR 24): the benchmark has no cell
    below seq 2048; what they cost there is in PERF.md section 5.
    """
    if mask is not None:
        return False
    if jax.default_backend() != "tpu":
        return False
    seq, d_qk = q.shape[1], q.shape[-1]
    return seq >= 1024 and d_qk % 64 == 0
