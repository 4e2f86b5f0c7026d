"""Selective state-space recurrence of Mamba-2 in its chunked SSD form.

The recurrence, a head ``h`` reading group ``g = h // (H // G)``::

    H_t = exp(dt_t,h * a_h) * H_{t-1} + dt_t,h * x_t,h (x) B_t,g     (P x N)
    y_t,h = H_t @ C_t,g                                      H_0 = 0

is computed a chunk of ``Q`` positions at a time (Dao & Gu 2024, "state
space duality"): inside a chunk the positions see each other through one
masked (Q, Q) product, ``(C B^T * decay) @ (dt x)``, the attention-like
dual; between chunks only the (P, N) state at each chunk's end is carried,
by a ``lax.scan`` over the chunks (``_chunk_states``). The per-token loop
of L steps becomes four batched matrix products and L / Q elementwise steps.

Two forms of the one algorithm, chosen by what ``ssd_scan`` can see
(``takes_kernel``) and by nothing else:

- ``form="kernel"``: on a TPU at the shapes Mosaic tiles (chunk 128, state
  a multiple of 128, head 64 or 128, at most 8 heads a group). The
  products of a chunk run in Pallas (Mosaic) kernels, a grid cell a
  (chunk, group) with the group's heads inside it so that ``C B^T`` is
  made once a group: ``ssd_fwd_state`` (what a chunk adds to the state by
  its own end) before the carry and ``ssd_fwd_out`` (the masked product and
  the read-out of the carried state) after it; backward ``ssd_bwd_out`` and
  ``ssd_bwd_state`` behind one ``jax.custom_vjp``, which rebuild ``C B^T``
  and each head's (Q, Q) decay tile in VMEM from the kernels' inputs.
  Nothing with a (Q, Q) face is written to HBM, forward or backward;
  residuals are the inputs and the chunk states. The carry itself stays
  the ``lax.scan`` of ``_chunk_states`` under XLA's autodiff.
- ``form="chunked"``: everything else (a CPU, tier-1's chunks of 4-64 and
  heads of 8, a state of 16): plain ``jax.numpy`` einsums, differentiable
  by autodiff. It is also the kernels' oracle in the tests.

Same mathematics and precision in both: the products take their operands
in the compute dtype (bf16 under the training policy) and accumulate in
float32; ``C B^T * decay`` and the carried state's copy for its read-out
are rounded to the compute dtype before their products; the decays
(``exp`` of the cumulated ``dt * a``) and the carried state are float32
throughout. The D skip and the gate belong to ``nn.Mamba2``. Measured:
PERF.md section 6, PR 26.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from bigdl_tpu.ops.scopes import under_scope

_LANES = 128
_NT = (((1,), (1,)), ((), ()))          # a (M, K) x b (N, K) -> (M, N)
_TN = (((0,), (0,)), ((), ()))          # a (K, M) x b (K, N) -> (M, N)


def takes_kernel(backend, chunk, head_dim, state, heads_per_group) -> bool:
    """The path rule: the Mosaic kernels on a TPU where a chunk is one
    128-lane tile (chunk 128, as published), the state size a multiple of
    128, a head half a lane tile or a whole one (head size 64 or 128), and
    the heads of a group are at most 8 (one sublane tile of per-head rows)
    and fill whole lane tiles; the XLA form everywhere else."""
    return (backend == "tpu" and chunk == _LANES and state % _LANES == 0
            and head_dim in (64, _LANES) and heads_per_group <= 8
            and (heads_per_group * head_dim) % _LANES == 0)


def ssd_scan(x, dt, a, b, c, chunk: int = 128):
    """``x`` (B, L, H, P), ``dt`` (B, L, H) after its softplus, ``a`` (H,)
    negative, ``b`` and ``c`` (B, L, G, N) with G dividing H -> ``y``
    (B, L, H, P) in ``x``'s dtype. L need not be a multiple of ``chunk``:
    the tail is padded with ``dt = 0`` (decay 1, no input), which leaves
    the state and every real output as they are."""
    from bigdl_tpu.telemetry import get_registry, instruments
    kernel = takes_kernel(jax.default_backend(), chunk, x.shape[3],
                          b.shape[3], x.shape[2] // b.shape[2])
    # trace-time count, as bigdl_moe_dispatch_total: which form a compiled
    # program holds
    instruments(get_registry()).ssd_scan_total.labels(
        form="kernel" if kernel else "chunked").inc()
    with jax.named_scope("ssd_scan"):
        if kernel:
            return _ssd_kernel(x, dt, a, b, c, chunk)
        return _ssd_chunked(x, dt, a, b, c, chunk)


def _chunk_states(whole, local):
    """Between chunks: the state at each chunk's START, from the decay over
    each whole chunk (B, nc, G, R) and what the chunk adds by its own end
    (B, nc, G, R, P, N). Float32, one elementwise step a chunk, from a
    zero state."""
    def carry_on(state, inp):
        keep, add = inp
        return keep[..., None, None] * state + add, state

    _, before = jax.lax.scan(
        carry_on, jnp.zeros(local.shape[:1] + local.shape[2:], jnp.float32),
        (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(local, 1, 0)))
    return jnp.moveaxis(before, 0, 1)


def _padded(q, length, *arrays):
    pad = (-length) % q
    if pad:
        arrays = tuple(jnp.pad(t, [(0, 0), (0, pad)]
                               + [(0, 0)] * (t.ndim - 2)) for t in arrays)
    return arrays


# ------------------------------------------------------------- the XLA form

def _ssd_chunked(x, dt, a, b, c, q):
    bsz, length, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r = h // g                              # heads reading one group
    cd = x.dtype
    f32 = jnp.float32
    x, dt, b, c = _padded(q, length, x, dt, b, c)
    nc = x.shape[1] // q

    dt = dt.astype(f32).reshape(bsz, nc, q, g, r)
    da = dt * a.astype(f32).reshape(g, r)
    cs = jnp.cumsum(da, axis=2)             # log-decay from the chunk's start
    xd = (x.reshape(bsz, nc, q, g, r, p).astype(f32) * dt[..., None])
    b = b.reshape(bsz, nc, q, g, n)
    c = c.reshape(bsz, nc, q, g, n)

    # inside a chunk: position l reads s <= l at decay exp(cs_l - cs_s)
    cs_t = jnp.moveaxis(cs, 2, -1)          # (B, nc, G, R, Q)
    diff = cs_t[..., :, None] - cs_t[..., None, :]
    causal = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))   # (B,nc,G,R,Q,Q)
    cb = jnp.einsum("bclgn,bcsgn->bcgls", c, b,
                    preferred_element_type=f32)
    m = (cb[:, :, :, None] * decay).astype(cd)
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp", m, xd.astype(cd),
                   preferred_element_type=f32)

    # what each chunk adds to the state by its own end
    to_end = jnp.exp(cs[:, :, -1:] - cs)    # (B, nc, Q, G, R)
    local = jnp.einsum("bcsgn,bcsgrp->bcgrpn", b,
                       (xd * to_end[..., None]).astype(cd),
                       preferred_element_type=f32)

    before = _chunk_states(jnp.exp(cs[:, :, -1]), local)

    y_off = jnp.einsum("bclgn,bcgrpn->bclgrp", c, before.astype(cd),
                       preferred_element_type=f32)
    y = y + y_off * jnp.exp(cs)[..., None]
    y = y.reshape(bsz, nc * q, h, p)[:, :length]
    return y.astype(cd)


# ---------------------------------------------------------- the kernel form
#
# What a grid cell (batch, chunk, group) sees, R <= 8 the heads of the group:
#   x, y, dy, dx   (Q, R*P)    the group's heads side by side on the lanes
#   b, c, db, dc   (Q, N)
#   rows           (16, Q)     float32, lane-dense: each head's dt (rows
#                              0-7), then each head's cumulated log-decay cs
#                              (rows 8-15), along the chunk; their cotangents
#                              leave the same way
#   state tiles    (R*P, N)    float32 in HBM (the carry's); the carried
#                              state is rounded to the operands' dtype in
#                              VMEM, as the XLA form rounds it
# A head's per-position scalars scale the ROWS of a (Q, 128) tile of its
# lanes, and a (Q, Q) decay tile is a column minus a row: both need a row
# of ``rows`` down the sublanes, the same on every lane that wants it.
# Spreading a row over sublanes is free and a lane broadcast is a permute a
# vreg (the first form: 400 a cell, most of its bundles; the second did it
# on the MXU at a 128^3 pass each, which then bound the kernel), so a row
# is spread over sublanes and the (128, Q) tile TRANSPOSED: one pass
# through the XLU. Sums over a head's lanes go the same way back: the tile
# transposed, then summed down the sublanes, which leaves them as rows.
# A head of 64 is half a 128-lane tile: the per-head products are made a
# 128-lane UNIT at a time against both heads' columns and the head's half
# is selected (the MXU's pass is 128 wide either way), so that no tile is
# cut inside a vreg.

_R8 = 8             # rows a quantity takes in ``rows``


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _to_end(cs):
    """exp(cs_end - cs) of (8, Q) rows."""
    return jnp.exp(cs[:, cs.shape[1] - 1:] - cs)


@dataclasses.dataclass(frozen=True)
class _Heads:
    """The geometry of one group's heads inside a cell: static, and equal
    by value, so that the blocks of a model share one trace of a call."""
    q: int                      # chunk
    r: int                      # heads of a group
    p: int                      # head size
    # Pallas' interpreter runs on XLA's CPU backend, which has no bf16
    # product with a transposed operand: there the operands are widened
    # first (exact: a product of two bf16 values fits float32)
    widen: bool

    @property
    def per_unit(self):
        return _LANES // self.p

    @property
    def units(self):
        return self.r // self.per_unit

    def dot(self, a, b, dims=(((1,), (0,)), ((), ()))):
        if self.widen:
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)

    def dot_nt(self, a, b):
        return self.dot(a, b, _NT)

    def dot_tn(self, a, b):
        return self.dot(a, b, _TN)

    def lanes(self, u):
        return slice(u * _LANES, (u + 1) * _LANES)

    def heads(self, u):
        return range(u * self.per_unit, (u + 1) * self.per_unit)

    def own(self, k):
        """Mask of the lanes of a unit that its k-th head owns."""
        lane = _iota((self.q, _LANES), 1)
        return (lane >= k * self.p) & (lane < (k + 1) * self.p)

    def pick(self, per_head):
        """One (Q, 128) tile from a tile a head of a unit, each head's own
        lanes taken from its tile."""
        out = per_head[0]
        for k in range(1, self.per_unit):
            out = jnp.where(self.own(k), per_head[k], out)
        return out

    def only(self, k, tile):
        """``tile`` with the lanes of the other heads of its unit zeroed."""
        if self.per_unit == 1:
            return tile
        return jnp.where(self.own(k), tile, jnp.zeros_like(tile))

    def column(self, row):
        """(1, Q) row -> (Q, 128): its values down the sublanes, on every
        lane."""
        return jnp.broadcast_to(row, (_LANES, self.q)).T

    def spread(self, u, rows):
        """(8, Q) rows -> (Q, 128): the row of each head of unit ``u`` down
        the sublanes, on the head's own lanes."""
        return jnp.concatenate(
            [jnp.broadcast_to(rows[h:h + 1], (self.p, self.q))
             for h in self.heads(u)], axis=0).T

    def sums(self, u, tile):
        """(Q, 128) float32 tile of unit ``u`` -> [(head, (1, Q) row of the
        sums over the head's lanes)]"""
        t = tile.T
        return [(h, jnp.sum(t[k * self.p:(k + 1) * self.p], axis=0,
                            keepdims=True))
                for k, h in enumerate(self.heads(u))]


def _rows_out(ref, first, by_head):
    """(1, Q) rows a head -> rows ``first + head`` of a ``rows`` block."""
    for h, row in by_head:
        ref[0, 0, first + h:first + h + 1, :] = row


def _fwd_state_kernel(x_ref, rows_ref, b_ref, local_ref, *, hd):
    # local (R*P, N) = (dt x * exp(cs_end - cs))^T B
    rows = rows_ref[0, 0]
    scale = rows[:_R8] * _to_end(rows[_R8:])
    for u in range(hd.units):
        at = hd.lanes(u)
        xw = x_ref[0, :, at].astype(jnp.float32) * hd.spread(u, scale)
        local_ref[0, 0, 0, at, :] = hd.dot_tn(xw.astype(x_ref.dtype),
                                              b_ref[0])


def _fwd_out_kernel(x_ref, rows_ref, b_ref, c_ref, before_ref, y_ref, *, hd):
    q = hd.q
    cd = x_ref.dtype
    rows = rows_ref[0, 0]
    dt, cs = rows[:_R8], rows[_R8:]
    grow = jnp.exp(cs)
    c = c_ref[0]
    seen = _iota((q, q), 1) <= _iota((q, q), 0)             # s <= l
    cb = jnp.where(seen, hd.dot_nt(c, b_ref[0]), 0.0)       # (l, s) f32
    for u in range(hd.units):
        at = hd.lanes(u)
        xd = (x_ref[0, :, at].astype(jnp.float32) * hd.spread(u, dt)
              ).astype(cd)
        per_head = []
        for h in hd.heads(u):
            # position l reads s <= l at decay exp(cs_l - cs_s) <= 1
            decay = jnp.exp(jnp.minimum(
                hd.column(cs[h:h + 1]) - cs[h:h + 1], 0.0))
            per_head.append(hd.dot((cb * decay).astype(cd), xd))
        y = hd.pick(per_head) \
            + hd.dot_nt(c, before_ref[0, 0, 0, at, :].astype(cd)) \
            * hd.spread(u, grow)
        y_ref[0, :, at] = y.astype(y_ref.dtype)


def _bwd_out_kernel(x_ref, rows_ref, b_ref, c_ref, before_ref, dy_ref,
                    dx_ref, drows_ref, db_ref, dc_ref, dbefore_ref, *, hd):
    # Everything on TRANSPOSED (s, l) tiles, so that M^T dy and dy-by-x need
    # no tile transposed but d(C B^T), once. With T = dM * M, the cotangent
    # of cs is T's sum over s (at l) minus its sum over l (at s). The first
    # is a sum down the sublanes and leaves as a row; the second, a sum
    # along the lanes of a (Q, Q) tile a head, is not made: it equals
    # sum_p (dt x) * (M^T dy), which is here anyway. Both take M as the
    # products do, rounded to the operands' dtype, so that over a chunk
    # they cancel as they must.
    q = hd.q
    cd = x_ref.dtype
    f32 = jnp.float32
    rows = rows_ref[0, 0]
    dt, cs = rows[:_R8], rows[_R8:]
    grow = jnp.exp(cs)
    b, c = b_ref[0], c_ref[0]
    seen = _iota((q, q), 1) >= _iota((q, q), 0)             # l >= s
    cbt = jnp.where(seen, hd.dot_nt(b, c), 0.0)             # (s, l) f32
    dcbt = jnp.zeros((q, q), f32)
    drows_ref[0, 0] = jnp.zeros((2 * _R8, q), f32)
    dc = jnp.zeros(c.shape, f32)
    for u in range(hd.units):
        at = hd.lanes(u)
        x = x_ref[0, :, at].astype(f32)
        dtw = hd.spread(u, dt)
        xd = (x * dtw).astype(cd)
        dy = dy_ref[0, :, at]
        before = before_ref[0, 0, 0, at, :].astype(cd)      # (128, N)
        # the carried state's read-out is (C before^T) * exp(cs_l)
        dyg = dy.astype(f32) * hd.spread(u, grow)
        g = dyg.astype(cd)
        dc = dc + hd.dot(g, before)
        dbefore_ref[0, 0, 0, at, :] = hd.dot_tn(g, c)
        per_head, at_l = [], []
        for k, h in enumerate(hd.heads(u)):
            decay = jnp.exp(jnp.minimum(
                cs[h:h + 1] - hd.column(cs[h:h + 1]), 0.0))     # (s, l)
            mt = (cbt * decay).astype(cd)
            per_head.append(hd.dot(mt, dy))                 # M^T dy
            dmt = hd.dot_nt(hd.only(k, xd), dy)
            dcbt = dcbt + dmt * decay
            at_l.append(jnp.sum(dmt * mt.astype(f32), axis=0, keepdims=True))
        dxd = hd.pick(per_head)
        dx_ref[0, :, at] = (dxd * dtw).astype(dx_ref.dtype)
        _rows_out(drows_ref, 0, hd.sums(u, dxd * x))
        at_s = hd.sums(u, dyg * hd.dot_nt(c, before) - xd.astype(f32) * dxd)
        _rows_out(drows_ref, _R8,
                  [(h, s_ + l_) for (h, s_), l_ in zip(at_s, at_l)])
    dcbt = jnp.where(seen, dcbt, 0.0)       # the mask lies on C B^T
    db_ref[0] = hd.dot(dcbt.astype(cd), c).astype(db_ref.dtype)
    dc_ref[0] = (dc + hd.dot(dcbt.T.astype(cd), b)).astype(dc_ref.dtype)


def _bwd_state_kernel(x_ref, rows_ref, b_ref, dlocal_ref, dx_in, drows_in,
                      db_in, dx_ref, drows_ref, db_ref, *, hd):
    # local = (x w)^T B with w = dt * exp(cs_end - cs) a head and position:
    # d x = d(x w) * w, and both d dt and d cs are d w's, the sum over a
    # head's lanes of d(x w) * x, times what w keeps of dt and of cs. All
    # three are added to what ``ssd_bwd_out`` found, in its own arrays.
    q = hd.q
    cd = x_ref.dtype
    f32 = jnp.float32
    rows = rows_ref[0, 0]
    to_end = _to_end(rows[_R8:])
    scale = rows[:_R8] * to_end                             # w, as rows
    b = b_ref[0]
    drows_ref[0, 0] = drows_in[0, 0]
    last = _iota((1, q), 1) == q - 1
    xws = []
    for u in range(hd.units):
        at = hd.lanes(u)
        x = x_ref[0, :, at].astype(f32)
        w = hd.spread(u, scale)
        dxw = hd.dot_nt(b, dlocal_ref[0, 0, 0, at, :].astype(cd))
        dx_ref[0, :, at] = (dx_in[0, :, at].astype(f32) + dxw * w
                            ).astype(dx_ref.dtype)
        xws.append((x * w).astype(cd))
        for h, dw in hd.sums(u, dxw * x):
            drows_ref[0, 0, h:h + 1, :] += dw * to_end[h:h + 1]
            # w = .. exp(cs_end - cs): d cs -= v, d cs_end += sum(v)
            v = dw * scale[h:h + 1]
            drows_ref[0, 0, _R8 + h:_R8 + h + 1, :] += jnp.where(
                last, jnp.sum(v, axis=1, keepdims=True), 0.0) - v
    db_ref[0] = (db_in[0].astype(f32) + hd.dot(
        jnp.concatenate(xws, axis=1), dlocal_ref[0, 0, 0].astype(cd))
    ).astype(db_ref.dtype)


# Chunks a grid step takes, where their number allows: half as many steps
# (0.35 us each) and longer DMAs; 2 measured 4-5% under 1 at the Nemotron
# cell's shape, and at 4 ``ssd_bwd_out`` no longer fits its 16 MiB of VMEM
_CHUNKS_A_CELL = 2


def _each_chunk(kernel, kinds, q, n):
    """``kernel``, written for one chunk, over the ``n`` chunks of a grid
    cell's blocks: a loop over views of the refs."""
    tokens = lambda j: (slice(None), pl.ds(j * q, q))
    at = {"wide": tokens, "bc": tokens,
          "rows": lambda j: (slice(None), slice(None), slice(None),
                             pl.ds(pl.multiple_of(j * q, _LANES), q)),
          "state": lambda j: (slice(None), pl.ds(j, 1))}

    def cell(*refs, hd):
        def chunk(j, _):
            kernel(*(ref.at[at[k](j)] for k, ref in zip(kinds, refs)),
                   hd=hd)
            return 0
        lax.fori_loop(0, n, chunk, 0)
    return cell


def _call(kernel, name, hd, interpret, ins, outs, aliases=None):
    """One Mosaic call over the grid (batch, chunks, groups). ``ins`` are
    (kind, array) with x, the rows and B first; ``outs`` (kind, dtype);
    ``aliases`` {input: output} for outputs written over an input."""
    x, rows, b = ins[0][1], ins[1][1], ins[2][1]
    bsz, length, g = x.shape[0], x.shape[1], rows.shape[1]
    wide, n, q = x.shape[2] // g, b.shape[2] // g, hd.q
    nc = length // q
    per = _CHUNKS_A_CELL if nc % _CHUNKS_A_CELL == 0 else 1
    spec = {"wide": pl.BlockSpec((1, per * q, wide),
                                 lambda i, j, k: (i, j, k)),
            "bc": pl.BlockSpec((1, per * q, n), lambda i, j, k: (i, j, k)),
            "rows": pl.BlockSpec((1, 1, 2 * _R8, per * q),
                                 lambda i, j, k: (i, k, 0, j)),
            "state": pl.BlockSpec((1, per, 1, wide, n),
                                  lambda i, j, k: (i, j, k, 0, 0))}
    shape = {"wide": x.shape, "bc": b.shape, "rows": rows.shape,
             "state": (bsz, nc, g, wide, n)}
    kinds = [k for k, _ in ins] + [k for k, _ in outs]
    return pl.pallas_call(
        functools.partial(_each_chunk(kernel, kinds, q, per), hd=hd),
        out_shape=tuple(jax.ShapeDtypeStruct(shape[k], d) for k, d in outs),
        grid=(bsz, nc // per, g),
        in_specs=[spec[k] for k, _ in ins],
        out_specs=tuple(spec[k] for k, _ in outs),
        input_output_aliases=aliases or {},
        interpret=interpret,
        name=name,
    )(*(t for _, t in ins))


# Each call is a jit of its own: the blocks of a model make the same calls
# at the same shapes, and a jit inside a jit is traced and lowered once for
# all of them (24 calls a Nemotron step cost 17 s of a warm start; 5 do not).

@functools.partial(jax.jit, static_argnums=(3, 4))
def _adds_call(x, rows, b, hd, interpret):
    """What each chunk adds to the state by its own end: (B, nc, G, R*P, N)
    float32."""
    return _call(_fwd_state_kernel, "ssd_fwd_state", hd, interpret,
                 [("wide", x), ("rows", rows), ("bc", b)],
                 [("state", jnp.float32)])[0]


@functools.partial(jax.jit, static_argnums=(5, 6))
def _outputs_call(x, rows, b, c, before, hd, interpret):
    """y (B, L, H*P): the masked product inside each chunk plus the
    read-out of the state the chunk started from."""
    return _call(_fwd_out_kernel, "ssd_fwd_out", hd, interpret,
                 [("wide", x), ("rows", rows), ("bc", b), ("bc", c),
                  ("state", before)], [("wide", x.dtype)])[0]


@functools.partial(jax.jit, static_argnums=(6, 7))
def _bwd_out_call(x, rows, b, c, before, dy, hd, interpret):
    """d x, d rows, d B, d C, d before of ``_outputs_call``."""
    f32 = jnp.float32
    return _call(
        _bwd_out_kernel, "ssd_bwd_out", hd, interpret,
        [("wide", x), ("rows", rows), ("bc", b), ("bc", c),
         ("state", before), ("wide", dy)],
        [("wide", x.dtype), ("rows", f32), ("bc", b.dtype), ("bc", c.dtype),
         ("state", f32)])


@functools.partial(jax.jit, static_argnums=(7, 8))
def _bwd_state_call(x, rows, b, dlocal, dx, drows, db, hd, interpret):
    """``_adds_call``'s share of d x, d rows and d B, added to ``dx``,
    ``drows`` and ``db`` in place."""
    return _call(
        _bwd_state_kernel, "ssd_bwd_state", hd, interpret,
        [("wide", x), ("rows", rows), ("bc", b), ("state", dlocal),
         ("wide", dx), ("rows", drows), ("bc", db)],
        [("wide", x.dtype), ("rows", jnp.float32), ("bc", b.dtype)],
        aliases={4: 0, 5: 1, 6: 2})


def _as_carried(local, hd):
    """(B, nc, G, R*P, N) <-> the carry's (B, nc, G, R, P, N)."""
    return local.reshape(local.shape[:3] + (hd.r, hd.p, local.shape[-1]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _chunks(x, rows, b, c, whole, hd, interpret):
    """The scan from its lane-dense operands: the two forward kernels
    around the carry. Where nothing is differentiated (a forward pass, the
    first pass under block remat) the carried state is rounded to the
    operands' dtype by XLA, which fuses that into the carry's loop so that
    the float32 states never reach HBM."""
    local = _adds_call(x, rows, b, hd, interpret)
    before = _chunk_states(whole, _as_carried(local, hd))
    return _outputs_call(x, rows, b, c,
                         before.astype(x.dtype).reshape(local.shape), hd,
                         interpret)


def _chunks_fwd(x, rows, b, c, whole, hd, interpret):
    # differentiated: the carry keeps its float32 states for its own
    # backward (XLA's autodiff of the lax.scan, whatever _chunk_states is
    # at trace time), and the kernel reads them as they are and rounds in
    # VMEM, as the XLA form rounds them
    local = _adds_call(x, rows, b, hd, interpret)
    before, carry_back = jax.vjp(_chunk_states, whole,
                                 _as_carried(local, hd))
    before = before.reshape(local.shape)
    return (_outputs_call(x, rows, b, c, before, hd, interpret),
            (x, rows, b, c, before, carry_back))


@under_scope("ssd_scan")
def _chunks_bwd(hd, interpret, res, dy):
    x, rows, b, c, before, carry_back = res
    dx, drows, db, dc, dbefore = _bwd_out_call(x, rows, b, c, before, dy, hd,
                                               interpret)
    # in the carry's own dtype (float32; a planted control's bf16)
    dwhole, dlocal = carry_back(
        _as_carried(dbefore, hd).astype(before.dtype))
    dx, drows, db = _bwd_state_call(x, rows, b, dlocal.reshape(before.shape),
                                    dx, drows, db, hd, interpret)
    return dx, drows, db, dc, dwhole


_chunks.defvjp(_chunks_fwd, _chunks_bwd)


def _ssd_kernel(x, dt, a, b, c, q, interpret=None):
    """The kernel form; ``interpret`` (tests on a CPU) runs the kernels in
    Pallas' interpreter."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bsz, length, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r = h // g
    hd = _Heads(q, r, p, interpret)
    f32 = jnp.float32
    x, dt, b, c = _padded(q, length, x, dt, b, c)
    full = x.shape[1]
    nc = full // q

    # (tokens, heads)-sized, in XLA, each head's values along the lanes:
    # dt and the log-decay cumulated from each chunk's start (a product
    # with a 0/1 triangle at full float32 precision: XLA's cumsum is a
    # reduce_window, 0.1-0.6 ms a call at this size on the v5e)
    dt = jnp.moveaxis(dt.astype(f32).reshape(bsz, full, g, r), 1, 3)
    da = (dt * a.astype(f32).reshape(g, r, 1)).reshape(bsz, g, r, nc, q)
    upto = (jnp.arange(q)[:, None] <= jnp.arange(q)[None, :]).astype(f32)
    cs = jnp.einsum("bgrcs,st->bgrct", da, upto,
                    precision=lax.Precision.HIGHEST)
    whole = jnp.moveaxis(jnp.exp(cs[..., -1]), 3, 1)        # (B, nc, G, R)
    tall = [(0, 0), (0, 0), (0, _R8 - r), (0, 0)]
    rows = jnp.concatenate([jnp.pad(dt, tall),
                            jnp.pad(cs.reshape(dt.shape), tall)], axis=2)

    y = _chunks(x.reshape(bsz, full, h * p), rows,
                b.reshape(bsz, full, g * n), c.reshape(bsz, full, g * n),
                whole, hd, interpret)
    return y.reshape(bsz, full, h, p)[:, :length]
