"""The gated delta-rule mixer's local part, everything of
``nn.GatedDeltaNet`` between its two projections that is neither a product
nor the recurrence, as Mosaic calls that read and write each array once::

    [q | k | v | z | b | a] = proj                the in-projection's output
    [q | k | v] = silu(causal_conv_k([q | k | v]))        pass A, before
    q = q / |q|_2 * d_k^-1/2,  k = k / |k|_2              (a head, pass A)
    o = gated_delta_rule(q, k, v, g(a), beta(b))
    y = RMSNorm_{d_v}(o) * w * silu(z)                    pass B, after

- ``delta_local_conv`` reads the ``conv_dim`` columns of ``proj`` where they
  lie (column block 0 of the operand, no slice), a row tile with the last
  rows of the tile before as a halo (zeros before row 0 of each sequence),
  and writes q, k and v as (B, H, L, d), the form the recurrence's calls
  take: its caller hands them on as transposed views, XLA cancels them
  against ``ops.delta_rule``'s own, and nothing is copied in between.
- ``delta_local_conv_bwd`` recomputes the pre-activation and the norms from
  its input tile, reads the cotangents of q, k and v in that form, writes
  the convolution's input cotangent and sums ``d conv_weight`` over the row
  tiles. Row tiles run last to first: what a tile's first rows owe the tile
  before is carried in VMEM, as ``mamba_local_conv_bwd`` carries it.
- ``delta_local_gate`` reads o (B, H, L, d_v) and z and writes the normed,
  weighted, gated output; ``delta_local_gate_bwd`` recomputes the row
  statistics, reads ``dy``, writes ``do`` (B, H, L, d_v) and ``dz`` and
  sums ``d norm_weight``.

**Heads that are no lane tiles.** A head is 96 or 192 lanes of a 128-lane
tile and 15 of them are odd, so a head's statistic is neither a lane
reduction of whole vregs nor of a fixed part of one. Every call therefore
takes a row tile in three steps: (1) a loop over strips of 32 rows and
chunks of 128 lanes makes what is to be summed a head (the squares; on the
way back also ``dn * n``) and leaves it in VMEM split in two bf16 parts
(sixteen bits of each float32 value; exact for the square of a bf16); (2)
ONE product of each part, all the tile's rows at once, with a 0/1 matrix
(lane, head) on the MXU sums the heads: (rows, 128) float32, a head a lane;
(3) a second loop reads a chunk's statistic back with a lane gather of
constant indices (one XLU permute a vreg, exact) and finishes. The MXU is
idle otherwise and the sums themselves cost no vector slot (the split into
parts does, ~6 a vreg); what steps 1 and 3 pass through VMEM (the
activation in float32) costs load and store slots that the convolution
leaves free.

``b`` and ``a`` are (tokens, heads)-sized: ``beta`` and ``g`` stay XLA's,
as ``mamba_local`` leaves ``dt``'s softplus, and so does the z slice of
``proj`` that pass B reads (``_gated_norm`` is handed the slice). The
cotangent of ``proj`` is XLA's to put together from its three parts (the
convolution's columns, ``dz`` and the ``b``, ``a`` columns): the two passes
sit behind a ``custom_vjp`` each, because ``nn.GatedDeltaNet`` keeps
``_recurrence_inputs`` and ``_gated_norm`` as separate methods that a
``correct`` gate's controls replace by name, so no one rule sees both ends.
In the compiled step it is never one array: the two backward products read
the parts as a fused concatenation.

Float32 arithmetic inside from operands in the compute dtype, rounded where
``nn.GatedDeltaNet``'s ``jax.numpy`` lines round (q, k, v at the write; y at
the write), so the two forms agree to a rounding. ``takes_kernel`` is the
path rule; ``nn.GatedDeltaNet`` runs its own lines wherever it says no.
Measured: PERF.md section 6, PR 47.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.mamba_local import (_CONV_STRIP, _HALO, _LANES, _f32,
                                       _fold, _mixed, _shifted, _sigmoid,
                                       _stage, _taps)
from bigdl_tpu.ops.scopes import under_scope

_STRIP = _CONV_STRIP            # rows a loop step takes, in every pass
_ROW_TILES = (256, 128)
_TILE_ELEMS = 1536 * 1024       # a block's elements at most (3 MB in bf16)
_VMEM = 64 << 20    # of the v5e's 128 MiB: blocks twice, scratch, spills


def takes_kernel(backend, dtype, length, heads, d_k, d_v, kernel) -> bool:
    """The path rule: the Mosaic calls on a TPU for bf16 operands where a
    key head is whole quarter lane tiles and a value head whole half tiles
    (so q and k together, and the value width, end on a half tile at
    least: a chunk's ragged end is 64 lanes or none), the convolution's
    columns (``heads * (2 d_k + d_v)``) are whole 128-lane tiles (pass A's
    block is column block 0 of the in-projection's output), the heads of q
    and k, a lane each after the product with the 0/1 matrix, fit one tile,
    the smallest row tile divides the length and the taps before a row fit
    the halo's upper half; ``nn.GatedDeltaNet``'s ``jax.numpy`` lines
    everywhere else. The published 30 heads of 96 / 192 and the 15 a chip
    holds are inside; tier-1's heads of 8 / 16 are not."""
    return (backend == "tpu" and dtype == jnp.bfloat16
            and d_k % (_LANES // 4) == 0 and d_v % (_LANES // 2) == 0
            and (heads * (2 * d_k + d_v)) % _LANES == 0
            and 2 * heads <= _LANES
            and length % _ROW_TILES[-1] == 0
            and 1 <= kernel <= _HALO // 2 + 1)


def _up(n):
    """``n`` rounded up to whole lane tiles."""
    return -(-n // _LANES) * _LANES


@dataclasses.dataclass(frozen=True)
class _Geo:
    """The layer's geometry: static, and equal by value, so that the blocks
    of a model share one trace of each call."""
    h: int                      # heads
    dk: int                     # key head size
    dv: int                     # value head size
    k: int                      # taps
    l2_eps: float               # under the root of the L2 norms
    eps: float                  # the gated RMSNorm's
    interpret: bool

    @property
    def qk(self):
        """Lanes of q and k together: the normalised ones."""
        return 2 * self.h * self.dk

    @property
    def d_value(self):
        return self.h * self.dv

    @property
    def conv_dim(self):
        return self.qk + self.d_value

    def rows(self, length, width):
        """The row tile for blocks ``width`` lanes wide."""
        fit = [t for t in _ROW_TILES
               if length % t == 0 and t * width <= _TILE_ELEMS]
        return fit[0] if fit else _ROW_TILES[-1]


def _heads_of(width, size):
    """For a row of ``width`` lanes in heads of ``size``: the 0/1 matrix
    (``_up(width)``, 128) bf16, lane ``l`` against its head ``l // size``
    (nothing against a lane past the row's end), and the gather's indices
    (8, ``_up(width)``) that read a head's lane back for each of its
    lanes."""
    lane = jnp.arange(_up(width))
    head = jnp.minimum(lane // size, _LANES - 1)
    ind = (head[:, None] == jnp.arange(_LANES)[None]) & (lane < width)[:, None]
    return (ind.astype(jnp.bfloat16),
            jnp.broadcast_to(head.astype(jnp.int32)[None], (8, lane.shape[0])))


def _each_chunk(lo, hi, width, body):
    """``body(at, wide)`` for the 128-lane chunks ``lo`` to ``hi`` of a
    ``width``-lane row, two a loop step (their chains interleave); ``at``
    the chunk's lanes, ``wide`` its whole tile where the row ends inside
    it (then a static slice), else ``at``."""
    pairs = (min(hi, width // _LANES) - lo) // 2

    def pair(i, _):
        for u in range(2):
            at = pl.ds(pl.multiple_of((lo + 2 * i + u) * _LANES, _LANES),
                       _LANES)
            body(at, at)
        return 0
    if pairs:
        lax.fori_loop(0, pairs, pair, 0)
    for c in range(lo + 2 * pairs, hi):
        wide = slice(c * _LANES, (c + 1) * _LANES)
        body(slice(c * _LANES, min((c + 1) * _LANES, width)), wide)


def _each_strip(rows, body, flip=False):
    strips = rows // _STRIP

    def strip(s, _):
        s = strips - 1 - s if flip else s
        body(pl.multiple_of(s * _STRIP, _STRIP))
        return 0
    lax.fori_loop(0, strips, strip, 0)


def _two_parts(x, hi_ref, lo_ref, rows, at):
    """float32 ``x`` into VMEM as two bf16 parts, sixteen bits of it."""
    hi = x.astype(jnp.bfloat16)
    hi_ref[rows, at] = hi
    lo_ref[rows, at] = (x - _f32(hi)).astype(jnp.bfloat16)


def _head_sums(hi_ref, lo_ref, ind_ref):
    """(rows, 128) float32: the two parts summed a head, a head a lane."""
    ind = ind_ref[...]
    return jnp.dot(hi_ref[...], ind, preferred_element_type=jnp.float32) \
        + jnp.dot(lo_ref[...], ind, preferred_element_type=jnp.float32)


def _of_head(stat, idx_ref, at, wide):
    """A strip's statistic (R, 128), a head a lane, read back for the
    lanes ``at`` of the chunk ``wide``."""
    idx = jnp.broadcast_to(idx_ref[0:1, wide], stat.shape)
    out = jnp.take_along_axis(stat, idx, axis=1)
    width = at.size if isinstance(at, pl.Slice) else at.stop - at.start
    return out if width == _LANES else out[:, :width]


def _clear_ragged(refs, width):
    """Zero the last tile of the parts where the row ends inside it: the
    product reads all of it, and 0 x what VMEM held is not 0."""
    if width % _LANES:
        for ref in refs:
            ref[:, _up(width) - _LANES:] = jnp.zeros(
                (ref.shape[0], _LANES), ref.dtype)


# The recurrence's calls take and give (B, H, L, d): a head's rows together,
# its d lanes from lane 0. The passes work on rows whose heads lie side by
# side on the lanes, so each pass moves a strip between the two forms in
# VMEM, a head at a time: a head of 96 lanes starts at lane 0, 96, 64 or 32
# of a tile, and the lane shifts are Mosaic's (a static slice of a value, a
# static slice of a ref at the store).

def _to_heads(dense_ref, rows, heads):
    """The strip ``rows`` of ``dense_ref`` (rows, lanes) out to head-major
    refs: ``heads`` is (ref (1, H, rows, d), first lane) a tensor."""
    for ref, first in heads:
        d = ref.shape[3]
        for h in range(ref.shape[1]):
            lo = first + h * d
            a = lo // _LANES * _LANES
            slab = dense_ref[rows, a:_up(lo + d)]
            ref[0, h, rows, :] = slab[:, lo - a:lo - a + d]


def _from_heads(dense_ref, rows, heads):
    """``_to_heads``' inverse: the strip of head-major refs into
    ``dense_ref``."""
    for ref, first in heads:
        d = ref.shape[3]
        for h in range(ref.shape[1]):
            dense_ref[rows, first + h * d:first + (h + 1) * d] = \
                ref[0, h, rows, :]


_PARAMS = pltpu.CompilerParams(dimension_semantics=("arbitrary",) * 2,
                               vmem_limit_bytes=_VMEM)


# ------------------------------------------------------------------- pass A
#
# A grid cell is (sequence b, row tile i) over all ``conv_dim`` lanes. The
# chunks under ``qk`` lanes are normalised a head; where ``qk`` ends inside
# a chunk (15 heads: 2,880 = 22.5 tiles) that chunk takes the normalised
# path with ``scale`` (1, conv_dim) as its mask: d_k^-1/2 on q's lanes, 1 on
# k's, 0 on v's.

def _qkv(geo, q_ref, k_ref, v_ref):
    """(ref, first lane) of q, k and v in a row of ``conv_dim`` lanes."""
    d_key = geo.h * geo.dk
    return (q_ref, 0), (k_ref, d_key), (v_ref, 2 * d_key)


def _silu_of(ext_ref, w_ref, r, at, k):
    """A strip's taps, pre-activation and its sigmoid for the lanes
    ``at``."""
    taps = _taps(ext_ref, r, at, k)
    pre = _mixed(taps, w_ref[:, at])
    return taps, pre, _sigmoid(pre)


def _conv_kernel(x_ref, halo_ref, w_ref, scale_ref, ind_ref, idx_ref,
                 q_ref, k_ref, v_ref, ext_ref, dense_ref, act_ref, hi_ref,
                 lo_ref, rs_ref, *, geo):
    _stage(ext_ref, x_ref, halo_ref, pl.program_id(1) == 0)
    tl, k = x_ref.shape[1], geo.k
    normed, chunks = _up(geo.qk) // _LANES, geo.conv_dim // _LANES

    def first(r):
        rows = pl.ds(r, _STRIP)

        def head_lanes(at, _):
            _, pre, sig = _silu_of(ext_ref, w_ref, r, at, k)
            act = pre * sig
            act_ref[rows, at] = act
            _two_parts(act * act, hi_ref, lo_ref, rows, at)

        def value_lanes(at, _):
            _, pre, sig = _silu_of(ext_ref, w_ref, r, at, k)
            dense_ref[rows, at] = (pre * sig).astype(dense_ref.dtype)
        _each_chunk(0, normed, geo.conv_dim, head_lanes)
        _each_chunk(normed, chunks, geo.conv_dim, value_lanes)
    _each_strip(tl, first)

    rs_ref[...] = lax.rsqrt(_head_sums(hi_ref, lo_ref, ind_ref) + geo.l2_eps)

    def last(r):
        rows = pl.ds(r, _STRIP)
        rs = rs_ref[rows, :]

        def head_lanes(at, wide):
            scale = scale_ref[:, at]
            unit = _of_head(rs, idx_ref, at, wide) * scale
            if geo.qk % _LANES:
                unit = jnp.where(scale > 0.0, unit, 1.0)
            dense_ref[rows, at] = (act_ref[rows, at] * unit).astype(
                dense_ref.dtype)
        _each_chunk(0, normed, geo.conv_dim, head_lanes)
        _to_heads(dense_ref, rows, _qkv(geo, q_ref, k_ref, v_ref))
    _each_strip(tl, last)


def _conv_bwd_kernel(x_ref, halo_ref, w_ref, scale_ref, ind_ref, idx_ref,
                     dq_ref, dk_ref, dv_ref, dx_ref, dw_ref, ext_ref,
                     dout_ref, ahead_ref, pre_ref, hi_ref, lo_ref, hi2_ref,
                     lo2_ref, a_ref, b_ref, *, geo):
    b, i = pl.program_id(0), pl.program_id(1)
    _stage(ext_ref, x_ref, halo_ref, i == pl.num_programs(1) - 1)
    tl, k = x_ref.shape[1], geo.k
    normed, chunks = _up(geo.qk) // _LANES, geo.conv_dim // _LANES

    @pl.when((b == 0) & (i == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(i == 0)            # the sequence's LAST rows: nothing after
    def _():
        ahead_ref[...] = jnp.zeros_like(ahead_ref)

    def first(r):
        rows = pl.ds(r, _STRIP)
        _from_heads(dout_ref, rows, _qkv(geo, dq_ref, dk_ref, dv_ref))

        def head_lanes(at, _):
            _, pre, sig = _silu_of(ext_ref, w_ref, r, at, k)
            pre_ref[rows, at] = pre
            act = pre * sig
            dn = _f32(dout_ref[rows, at]) * scale_ref[:, at]
            _two_parts(act * act, hi_ref, lo_ref, rows, at)
            _two_parts(dn * act, hi2_ref, lo2_ref, rows, at)
        _each_chunk(0, normed, geo.conv_dim, head_lanes)
    _each_strip(tl, first)

    # with n = act * rs: d act = rs (dn - n sum_head(dn n))
    #                          = rs dn - act (rs^3 sum_head(dn act))
    rs = lax.rsqrt(_head_sums(hi_ref, lo_ref, ind_ref) + geo.l2_eps)
    a_ref[...] = rs
    b_ref[...] = rs * rs * rs * _head_sums(hi2_ref, lo2_ref, ind_ref)

    def last(r):
        rows = pl.ds(r, _STRIP)
        a, bb = a_ref[rows, :], b_ref[rows, :]

        def finish(at, taps, pre, sig, dact):
            w = w_ref[:, at]
            dpre = dact * (sig * (1.0 + pre * (1.0 - sig)))
            for t in range(k):
                dw_ref[0, t, :, at] += _fold(dpre * taps[t])
            # row s of the input is tap t of row s + k - 1 - t: the strip's
            # rows under eight that are not read, over the first eight of
            # the strip after it
            after = jnp.concatenate([dpre[0:8], dpre, ahead_ref[:, at]],
                                    axis=0)
            ahead_ref[:, at] = dpre[0:8]
            din = _mixed([_shifted(after, 1 - k + t, _STRIP)
                          for t in range(k)], w)
            dx_ref[0, rows, at] = din.astype(dx_ref.dtype)

        def head_lanes(at, wide):
            taps = _taps(ext_ref, r, at, k)
            pre = pre_ref[rows, at]
            sig = _sigmoid(pre)
            scale = scale_ref[:, at]
            dout = _f32(dout_ref[rows, at])
            dact = _of_head(a, idx_ref, at, wide) * (dout * scale) \
                - (pre * sig) * _of_head(bb, idx_ref, at, wide)
            if geo.qk % _LANES:
                dact = jnp.where(scale > 0.0, dact, dout)
            finish(at, taps, pre, sig, dact)

        def value_lanes(at, _):
            taps, pre, sig = _silu_of(ext_ref, w_ref, r, at, k)
            finish(at, taps, pre, sig, _f32(dout_ref[rows, at]))
        _each_chunk(0, normed, geo.conv_dim, head_lanes)
        _each_chunk(normed, chunks, geo.conv_dim, value_lanes)
    _each_strip(tl, last, flip=True)


def _conv_specs(geo, tl, nt, flip):
    """(x, halo, taps, scale, 0/1 matrix, indices) specs of pass A over the
    grid (sequences, row tiles); ``flip`` runs the row tiles last to
    first."""
    c, per = geo.conv_dim, tl // _HALO
    row = (lambda i: nt - 1 - i) if flip else (lambda i: i)
    whole = lambda b, i: (0, 0)
    return [
        pl.BlockSpec((1, tl, c), lambda b, i: (b, row(i), 0)),
        pl.BlockSpec((1, _HALO, c), lambda b, i: (
            b, jnp.maximum(row(i) * per - 1, 0), 0)),
        pl.BlockSpec((geo.k, c), whole),
        pl.BlockSpec((1, c), whole),
        pl.BlockSpec((_up(geo.qk), _LANES), whole),
        pl.BlockSpec((8, _up(geo.qk)), whole),
    ], row


def _conv_operands(conv_weight, geo):
    """The taps (k, conv_dim) float32, the lanes' scale (1, conv_dim), the
    0/1 matrix and the gather's indices of q's and k's heads."""
    f32 = jnp.float32
    d_key = geo.h * geo.dk
    scale = jnp.concatenate([jnp.full((d_key,), geo.dk ** -0.5, f32),
                             jnp.ones((d_key,), f32),
                             jnp.zeros((geo.d_value,), f32)])[None]
    return (conv_weight.astype(f32).T, scale) \
        + _heads_of(geo.qk, geo.dk)


# Each call is a jit of its own, as the recurrence's are: the blocks of a
# model make the same calls at the same shapes, and a jit inside a jit is
# traced and lowered once for all of them.

def _head_major(geo, bsz, length, tl, row, dtype):
    """Shapes and block specs of q, k and v as the recurrence's calls take
    them: (B, H, L, d), a row tile of every head a block."""
    shapes = tuple(jax.ShapeDtypeStruct((bsz, geo.h, length, d), dtype)
                   for d in (geo.dk, geo.dk, geo.dv))
    specs = tuple(pl.BlockSpec((1, geo.h, tl, d),
                               lambda b, i: (b, 0, row(i), 0))
                  for d in (geo.dk, geo.dk, geo.dv))
    return shapes, specs


@functools.partial(jax.jit, static_argnums=(2,))
def _conv_call(proj, conv_weight, geo):
    """q, k (B, H, L, d_k) and v (B, H, L, d_v) as the recurrence reads
    them, from ``proj`` (B, L, conv_dim + ...) and ``conv_weight``
    (conv_dim, k)."""
    bsz, length, _ = proj.shape
    c, kp = geo.conv_dim, _up(geo.qk)
    tl = geo.rows(length, c)
    nt = length // tl
    ins, row = _conv_specs(geo, tl, nt, False)
    shapes, outs = _head_major(geo, bsz, length, tl, row, proj.dtype)
    return pl.pallas_call(
        functools.partial(_conv_kernel, geo=geo),
        out_shape=shapes,
        grid=(bsz, nt),
        in_specs=ins,
        out_specs=outs,
        scratch_shapes=[pltpu.VMEM((_HALO + tl, c), proj.dtype),
                        pltpu.VMEM((tl, c), proj.dtype),
                        pltpu.VMEM((tl, kp), jnp.float32),
                        pltpu.VMEM((tl, kp), jnp.bfloat16),
                        pltpu.VMEM((tl, kp), jnp.bfloat16),
                        pltpu.VMEM((tl, _LANES), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=geo.interpret,
        name="delta_local_conv",
    )(proj, proj, *_conv_operands(conv_weight, geo))


@functools.partial(jax.jit, static_argnums=(5,))
def _conv_bwd_call(proj, conv_weight, dq, dk, dv, geo):
    """The convolution's input cotangent (B, L, conv_dim) and (k, 8,
    conv_dim) float32 partial sums of the taps' cotangents, from the
    cotangents of q, k and v, (B, H, L, d) each."""
    bsz, length, _ = proj.shape
    c, kp, k = geo.conv_dim, _up(geo.qk), geo.k
    tl = geo.rows(length, c)
    nt = length // tl
    ins, row = _conv_specs(geo, tl, nt, True)
    _, cotangents = _head_major(geo, bsz, length, tl, row, proj.dtype)
    parts = [pltpu.VMEM((tl, kp), jnp.bfloat16)] * 4
    stats = [pltpu.VMEM((tl, _LANES), jnp.float32)] * 2
    return pl.pallas_call(
        functools.partial(_conv_bwd_kernel, geo=geo),
        out_shape=(jax.ShapeDtypeStruct((bsz, length, c), proj.dtype),
                   jax.ShapeDtypeStruct((1, k, 8, c), jnp.float32)),
        grid=(bsz, nt),
        in_specs=ins + list(cotangents),
        out_specs=(pl.BlockSpec((1, tl, c), lambda b, i: (b, row(i), 0)),
                   pl.BlockSpec((1, k, 8, c), lambda b, i: (0, 0, 0, 0))),
        scratch_shapes=[pltpu.VMEM((_HALO + tl, c), proj.dtype),
                        pltpu.VMEM((tl, c), proj.dtype),
                        pltpu.VMEM((8, c), jnp.float32),
                        pltpu.VMEM((tl, kp), jnp.float32)] + parts + stats,
        compiler_params=_PARAMS,
        interpret=geo.interpret,
        name="delta_local_conv_bwd",
    )(proj, proj, *_conv_operands(conv_weight, geo), dq, dk, dv)


# ------------------------------------------------------------------- pass B
#
# A grid cell is (sequence b, row tile i) over all ``d_value`` lanes, which
# end inside a tile at 15 heads (2,880 = 22.5). With rs = rsqrt(mean_head(
# o^2) + eps), n = o rs and gate = z sigmoid(z): y = n w gate.

def _gate_of(z_ref, rows, at):
    z = _f32(z_ref[0, rows, at])
    sig = _sigmoid(z)
    return z, sig, z * sig


def _gate_kernel(o_ref, z_ref, w_ref, ind_ref, idx_ref, out_ref, dense_ref,
                 hi_ref, lo_ref, rs_ref, *, geo):
    tl, width = z_ref.shape[1], geo.d_value
    chunks = _up(width) // _LANES
    _clear_ragged((hi_ref, lo_ref), width)

    def first(r):
        rows = pl.ds(r, _STRIP)
        _from_heads(dense_ref, rows, ((o_ref, 0),))

        def squares(at, _):
            o = _f32(dense_ref[rows, at])
            _two_parts(o * o, hi_ref, lo_ref, rows, at)
        _each_chunk(0, chunks, width, squares)
    _each_strip(tl, first)

    rs_ref[...] = lax.rsqrt(_head_sums(hi_ref, lo_ref, ind_ref)
                            * (1.0 / geo.dv) + geo.eps)

    def last(r):
        rows = pl.ds(r, _STRIP)
        rs = rs_ref[rows, :]

        def gated(at, wide):
            _, _, gate = _gate_of(z_ref, rows, at)
            n = _f32(dense_ref[rows, at]) * _of_head(rs, idx_ref, at, wide)
            out_ref[0, rows, at] = (n * w_ref[:, at] * gate).astype(
                out_ref.dtype)
        _each_chunk(0, chunks, width, gated)
    _each_strip(tl, last)


def _gate_bwd_kernel(o_ref, z_ref, w_ref, ind_ref, idx_ref, dout_ref, do_ref,
                     dz_ref, sums_ref, dense_ref, back_ref, hi_ref, lo_ref,
                     hi2_ref, lo2_ref, a_ref, b_ref, *, geo):
    tl, width = z_ref.shape[1], geo.d_value
    chunks = _up(width) // _LANES
    _clear_ragged((hi_ref, lo_ref, hi2_ref, lo2_ref), width)

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    def first(r):
        rows = pl.ds(r, _STRIP)
        _from_heads(dense_ref, rows, ((o_ref, 0),))

        def products(at, _):
            _, _, gate = _gate_of(z_ref, rows, at)
            o = _f32(dense_ref[rows, at])
            dn = _f32(dout_ref[0, rows, at]) * w_ref[:, at] * gate
            _two_parts(o * o, hi_ref, lo_ref, rows, at)
            _two_parts(dn * o, hi2_ref, lo2_ref, rows, at)
        _each_chunk(0, chunks, width, products)
    _each_strip(tl, first)

    # d o = rs (dn - n mean_head(dn n)) = rs dn - o (rs^3 mean_head(dn o))
    rs = lax.rsqrt(_head_sums(hi_ref, lo_ref, ind_ref) * (1.0 / geo.dv)
                   + geo.eps)
    a_ref[...] = rs
    b_ref[...] = rs * rs * rs * _head_sums(hi2_ref, lo2_ref, ind_ref) \
        * (1.0 / geo.dv)

    def last(r):
        rows = pl.ds(r, _STRIP)
        a, bb = a_ref[rows, :], b_ref[rows, :]

        def cotangents(at, wide):
            z, sig, gate = _gate_of(z_ref, rows, at)
            o = _f32(dense_ref[rows, at])
            w = w_ref[:, at]
            dout = _f32(dout_ref[0, rows, at])
            rs_here = _of_head(a, idx_ref, at, wide)
            n = o * rs_here
            back_ref[rows, at] = (
                rs_here * (dout * w * gate)
                - o * _of_head(bb, idx_ref, at, wide)).astype(back_ref.dtype)
            dz_ref[0, rows, at] = (
                dout * (n * w) * (sig * (1.0 + z * (1.0 - sig)))).astype(
                    dz_ref.dtype)
            sums_ref[0, :, at] += _fold(dout * n * gate)
        _each_chunk(0, chunks, width, cotangents)
        _to_heads(back_ref, rows, ((do_ref, 0),))
    _each_strip(tl, last)


def _gate_specs(geo, tl):
    width = geo.d_value
    whole = lambda b, i: (0, 0)
    heads = pl.BlockSpec((1, geo.h, tl, geo.dv), lambda b, i: (b, 0, i, 0))
    tile = pl.BlockSpec((1, tl, width), lambda b, i: (b, i, 0))
    return heads, tile, [pl.BlockSpec((1, width), whole),
                         pl.BlockSpec((_up(width), _LANES), whole),
                         pl.BlockSpec((8, _up(width)), whole)]


def _gate_operands(norm_weight, geo):
    """The norm's weight over its heads' lanes (1, d_value) float32, the
    0/1 matrix and the gather's indices of the value heads."""
    return (jnp.tile(norm_weight.astype(jnp.float32), geo.h)[None],) \
        + _heads_of(geo.d_value, geo.dv)


@functools.partial(jax.jit, static_argnums=(3,))
def _gate_call(o, z, norm_weight, geo):
    """``RMSNorm_{d_v}(o) * w * silu(z)`` a head: (B, L, d_value) from
    ``o`` (B, H, L, d_v) as the recurrence's call gives it, ``z`` (B, L,
    d_value) and ``norm_weight`` (d_v,)."""
    bsz, length, width = z.shape
    tl = geo.rows(length, width)
    heads, tile, rest = _gate_specs(geo, tl)
    wide = (tl, _up(width))
    return pl.pallas_call(
        functools.partial(_gate_kernel, geo=geo),
        out_shape=jax.ShapeDtypeStruct(z.shape, z.dtype),
        grid=(bsz, length // tl),
        in_specs=[heads, tile] + rest,
        out_specs=tile,
        scratch_shapes=[pltpu.VMEM(wide, o.dtype),
                        pltpu.VMEM(wide, jnp.bfloat16),
                        pltpu.VMEM(wide, jnp.bfloat16),
                        pltpu.VMEM((tl, _LANES), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=geo.interpret,
        name="delta_local_gate",
    )(o, z, *_gate_operands(norm_weight, geo))


@functools.partial(jax.jit, static_argnums=(4,))
def _gate_bwd_call(o, z, norm_weight, dout, geo):
    """``do`` (B, H, L, d_v), ``dz`` (B, L, d_value) and (1, 8, d_value)
    float32 partial sums of the norm weight's cotangent, a lane (its heads
    and the eight rows are summed outside)."""
    bsz, length, width = z.shape
    tl = geo.rows(length, width)
    heads, tile, rest = _gate_specs(geo, tl)
    wide = (tl, _up(width))
    dense = [pltpu.VMEM(wide, o.dtype)] * 2      # o, and do before it goes
    parts = [pltpu.VMEM(wide, jnp.bfloat16)] * 4
    stats = [pltpu.VMEM((tl, _LANES), jnp.float32)] * 2
    return pl.pallas_call(
        functools.partial(_gate_bwd_kernel, geo=geo),
        out_shape=(jax.ShapeDtypeStruct(o.shape, o.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct((1, 8, width), jnp.float32)),
        grid=(bsz, length // tl),
        in_specs=[heads, tile] + rest + [tile],
        out_specs=(heads, tile,
                   pl.BlockSpec((1, 8, width), lambda b, i: (0, 0, 0))),
        scratch_shapes=dense + parts + stats,
        compiler_params=_PARAMS,
        interpret=geo.interpret,
        name="delta_local_gate_bwd",
    )(o, z, *_gate_operands(norm_weight, geo), dout)


# ----------------------------------------------------- the two differentiable

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _conv(proj, conv_weight, geo):
    return _conv_call(proj, conv_weight, geo)


def _conv_fwd(proj, conv_weight, geo):
    return _conv_call(proj, conv_weight, geo), (proj, conv_weight)


@under_scope("delta_local")
def _conv_bwd(geo, res, cotangents):
    proj, conv_weight = res
    dx, taps = _conv_bwd_call(proj, conv_weight, *cotangents, geo)
    # the other columns' cotangents come from their own readers; XLA sums
    # the parts into one buffer
    wide = jnp.pad(dx, ((0, 0), (0, 0), (0, proj.shape[2] - geo.conv_dim)))
    return wide, jnp.sum(taps[0], axis=1).T.astype(conv_weight.dtype)


_conv.defvjp(_conv_fwd, _conv_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gate(o, z, norm_weight, geo):
    return _gate_call(o, z, norm_weight, geo)


def _gate_fwd(o, z, norm_weight, geo):
    return _gate_call(o, z, norm_weight, geo), (o, z, norm_weight)


@under_scope("delta_local")
def _gate_bwd(geo, res, dout):
    o, z, norm_weight = res
    do, dz, sums = _gate_bwd_call(o, z, norm_weight, dout, geo)
    dw = jnp.sum(jnp.sum(sums[0], axis=0).reshape(geo.h, geo.dv), axis=0)
    return do, dz, dw.astype(norm_weight.dtype)


_gate.defvjp(_gate_fwd, _gate_bwd)


def _geo(heads, d_k, d_v, kernel, l2_eps, eps):
    """Off a TPU (tier-1's CPU with the path rule forced) the calls run in
    Pallas' interpreter."""
    return _Geo(heads, d_k, d_v, kernel, float(l2_eps), float(eps),
                jax.default_backend() != "tpu")


# Both take and give the (B, L, H, d) layout of ``nn.GatedDeltaNet``'s
# methods as a transposed VIEW of the calls' (B, H, L, d):
# ``ops.delta_rule``'s kernel form transposes its operands to (B, H, L, d)
# and its output back, and XLA cancels each pair, so between these calls
# and the recurrence's nothing is copied.

def conv_silu_norm(proj, conv_weight, *, heads, d_k, d_v, l2_eps):
    """``nn.GatedDeltaNet._recurrence_inputs``' q, k (B, L, H, d_k) and v
    (B, L, H, d_v), kernel form, from ``proj`` (B, L, conv_dim + ...) and
    ``conv_weight`` (conv_dim, k). Shapes as ``takes_kernel`` admits."""
    geo = _geo(heads, d_k, d_v, conv_weight.shape[1], l2_eps, 0.0)
    return tuple(jnp.swapaxes(t, 1, 2)
                 for t in _conv(proj, conv_weight, geo))


def gated_norm(o, z, norm_weight, *, eps):
    """``nn.GatedDeltaNet._gated_norm``, kernel form: ``o`` (B, L, H, d_v),
    ``z`` (B, L, H d_v), ``norm_weight`` (d_v,) -> (B, L, H d_v) in ``z``'s
    dtype."""
    heads, d_v = o.shape[2:]
    geo = _geo(heads, 0, d_v, 1, 0.0, eps)
    return _gate(jnp.swapaxes(o, 1, 2).astype(z.dtype), z, norm_weight, geo)
