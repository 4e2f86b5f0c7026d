"""The Mamba-2 mixer's local part, everything between its two projections
that is neither a product nor the scan, as Mosaic calls that read and write
each array once::

    [z | xBC | dt] = zxbcdt                       the in-projection's output
    x, B, C = split(silu(causal_conv_k(xBC) + b))          pass A, before
    y = ssd_scan(x, softplus(dt + dt_bias), -exp(A_log), B, C)
    out = GroupRMSNorm_G((y + D x) * silu(z)) * w          pass B, after

``around_scan`` is the whole of it behind one ``jax.custom_vjp``, the scan
(``ops.ssd_scan``, kernel or chunked as its own rule says) called inside,
so that the backward is ONE straight line and no cotangent is summed or
padded in the open:

- ``mamba_local_conv`` reads the ``conv_dim`` columns of ``zxbcdt`` where
  they lie (a column offset of the block index, no slice), with the last
  rows of the row tile before as a halo (zeros before row 0 of each
  sequence), and writes x (B, L, H*P), B and C (B, L, G*N) as three arrays,
  the flat form the scan's kernels take.
- ``mamba_local_gate`` reads y, x and z (the first ``d_inner`` columns of
  ``zxbcdt``) and writes the normed, weighted output.
- ``mamba_local_gate_bwd`` recomputes the row statistics, writes ``dy``
  (the scan's cotangent; the skip's share of ``dx`` is ``D * dy``, which
  pass A's backward adds where it reads ``dx``) and ``dz`` into the first
  columns of the ONE (B, L, d_in_proj) buffer that becomes ``zxbcdt``'s
  cotangent, and sums ``d D`` and ``d norm_weight`` over the row tiles.
- ``mamba_local_conv_bwd`` recomputes the pre-activation from its input
  tile, reads the scan's three cotangents and ``dy``, writes the
  convolution's input cotangent into its columns of that buffer (aliased in
  and out) and sums ``d conv_weight`` and ``d conv_bias`` over the row
  tiles. Row tiles run last to first: what a tile's first rows owe the tile
  before is carried in VMEM.

``dt``'s softplus and ``a`` are (tokens, heads)-sized and stay XLA's; the
``H`` columns of ``d dt`` are written into the buffer's tail in place.

Float32 arithmetic inside from operands in the compute dtype, rounded where
``nn.Mamba2``'s ``jax.numpy`` lines round (the convolution's output, the
normed value before its weight, the output), so the two forms agree to a
rounding. ``takes_kernel`` is the path rule; ``nn.Mamba2`` runs its own
lines wherever it says no. Measured: PERF.md section 6, PR 43.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.scopes import under_scope
from bigdl_tpu.ops.ssd_scan import ssd_scan

_LANES = 128
_HALO = 16          # rows of the halo block: one packed bf16 sublane tile
_STRIP = 16         # rows a loop step of pass B takes
_CONV_STRIP = 32    # and of pass A: longer, the rows before are read again
_ROW_TILES = (512, 256, 128)
_TILE_ELEMS = 512 * 1024    # a block's elements at most (1 MB in bf16)
_VMEM = 48 << 20    # of the v5e's 128 MiB: blocks twice, scratch, spills


def takes_kernel(backend, dtype, length, d_inner, group_state, n_groups,
                 kernel) -> bool:
    """The path rule: the Mosaic calls on a TPU for bf16 operands where
    the state columns of one of B and C (``G * N``) are whole 128-lane
    tiles that divide ``d_inner`` (pass A's column tile, so x, B and C each
    start on a tile), a norm group is whole lane tiles, the smallest row
    tile divides the length and the taps before a row fit the halo's upper
    half; ``nn.Mamba2``'s ``jax.numpy`` lines everywhere else."""
    return (backend == "tpu" and dtype == jnp.bfloat16
            and group_state % _LANES == 0 and d_inner % group_state == 0
            and d_inner % n_groups == 0
            and (d_inner // n_groups) % _LANES == 0
            and length % _ROW_TILES[-1] == 0
            and 1 <= kernel <= _HALO // 2 + 1)


@dataclasses.dataclass(frozen=True)
class _Geo:
    """The layer's geometry: static, and equal by value, so that the blocks
    of a model share one trace of each call."""
    h: int                      # heads
    p: int                      # head size
    g: int                      # groups
    n: int                      # state size
    k: int                      # taps
    chunk: int                  # the scan's
    eps: float
    interpret: bool

    @property
    def d_inner(self):
        return self.h * self.p

    @property
    def tc(self):
        """Pass A's column tile: the columns of one of B and C."""
        return self.g * self.n

    @property
    def nx(self):
        """Column tiles of x; B is tile ``nx`` and C tile ``nx + 1``."""
        return self.d_inner // self.tc

    @property
    def conv_dim(self):
        return self.d_inner + 2 * self.tc

    @property
    def gw(self):
        """Lanes of a norm group."""
        return self.d_inner // self.g

    @property
    def tg(self):
        """Pass B's column tile: whole groups, about 1024 lanes."""
        m = max(1, 1024 // self.gw)
        while self.g % m:
            m -= 1
        return self.gw * m

    def rows(self, length, width):
        """The row tile for blocks ``width`` lanes wide."""
        fit = [t for t in _ROW_TILES
               if length % t == 0 and t * width <= _TILE_ELEMS]
        return fit[0] if fit else _ROW_TILES[-1]


def _f32(x):
    return x.astype(jnp.float32)


def _sigmoid(x):
    # through tanh: one transcendental a value and no division (an exact
    # float32 quotient is ~20 vector operations a vreg, and these kernels
    # are bound by those)
    return 0.5 * jnp.tanh(0.5 * x) + 0.5


def _fold(x):
    """(R, W) float32 -> (8, W): the rows summed eight apart, which adds
    whole vregs; the eight left are summed outside the kernel."""
    out = x[0:8]
    for r in range(8, x.shape[0], 8):
        out = out + x[r:r + 8]
    return out


# every grid is (column tiles, sequences, row tiles) and runs in order:
# accumulators and carries live across its steps
_PARAMS = pltpu.CompilerParams(dimension_semantics=("arbitrary",) * 3,
                               vmem_limit_bytes=_VMEM)


# ------------------------------------------------------------------- pass A
#
# A grid cell is (column tile j, sequence b, row tile i), rows innermost.
# The tile's rows lie in ``ext`` under the _HALO rows before them (the tile
# before's last, or zeros at a sequence's start), so a strip of R rows reads
# rows [r, r + _HALO + R) of ``ext`` and tap t of k is that value shifted
# down by k - 1 - t rows.

def _stage(ext_ref, x_ref, halo_ref, first):
    halo = halo_ref[0]
    ext_ref[0:_HALO] = jnp.where(first, jnp.zeros_like(halo), halo)
    ext_ref[_HALO:] = x_ref[0]


def _shifted(value, down, rows):
    """``rows`` rows of ``value`` from row 8 on, read ``down`` rows higher
    (negative: lower). A sublane rotation and a select a vreg; a slice at
    an odd row offset would drag its offset through every operation after
    it (half again the work at 16 rows)."""
    if down:
        value = pltpu.roll(value, down % value.shape[0], 0)
    return value[8:8 + rows]


def _taps(ext_ref, r, at, k):
    """The k shifted float32 (R, W) values of a strip, oldest tap first."""
    f = _f32(ext_ref[pl.ds(r, _HALO + _CONV_STRIP), at])[_HALO - 8:]
    return [_shifted(f, k - 1 - t, _CONV_STRIP) for t in range(k)]


def _mixed(values, w):
    """sum_t values[t] * w[t], the taps ``w`` (k, W) down the sublanes."""
    out = values[0] * w[0:1]
    for t in range(1, len(values)):
        out = out + values[t] * w[t:t + 1]
    return out


def _lane_chunks(width):
    return [slice(c, c + _LANES) for c in range(0, width, _LANES)]


def _conv_kernel(x_ref, halo_ref, w_ref, bias_ref, xo_ref, bo_ref, co_ref,
                 ext_ref, *, geo):
    j, i = pl.program_id(0), pl.program_id(2)
    _stage(ext_ref, x_ref, halo_ref, i == 0)
    strips = x_ref.shape[1] // _CONV_STRIP

    def run(out_ref):
        def strip(s, _):
            r = pl.multiple_of(s * _CONV_STRIP, _CONV_STRIP)
            for at in _lane_chunks(geo.tc):
                pre = _mixed(_taps(ext_ref, r, at, geo.k), w_ref[:, at]) \
                    + bias_ref[:, at]
                out_ref[0, pl.ds(r, _CONV_STRIP), at] = \
                    (pre * _sigmoid(pre)).astype(out_ref.dtype)
            return 0
        lax.fori_loop(0, strips, strip, 0)

    pl.when(j < geo.nx)(lambda: run(xo_ref))
    pl.when(j == geo.nx)(lambda: run(bo_ref))
    pl.when(j == geo.nx + 1)(lambda: run(co_ref))


def _conv_bwd_kernel(x_ref, halo_ref, w_ref, bias_ref, dspread_ref, dx_ref,
                     dy_ref, db_ref, dc_ref, wide_ref, out_ref, dw_ref,
                     ext_ref, ahead_ref, *, geo):
    del wide_ref                # aliased to ``out_ref``: its other columns
    j, b, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    k = geo.k
    _stage(ext_ref, x_ref, halo_ref, i == pl.num_programs(2) - 1)
    strips = x_ref.shape[1] // _CONV_STRIP

    @pl.when((b == 0) & (i == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(i == 0)            # the sequence's LAST rows: nothing after
    def _():
        ahead_ref[...] = jnp.zeros_like(ahead_ref)

    def run(cotangent):
        def strip(s, _):
            r = pl.multiple_of((strips - 1 - s) * _CONV_STRIP, _CONV_STRIP)
            for at in _lane_chunks(geo.tc):
                taps = _taps(ext_ref, r, at, k)
                w = w_ref[:, at]
                pre = _mixed(taps, w) + bias_ref[:, at]
                sig = _sigmoid(pre)
                dpre = cotangent(r, at) * (sig * (1.0 + pre * (1.0 - sig)))
                dw_ref[0, k, :, at] += _fold(dpre)
                for t in range(k):
                    dw_ref[0, t, :, at] += _fold(dpre * taps[t])
                # row s of the input is tap t of row s + k - 1 - t: the
                # strip's rows under eight that are not read, over the
                # first eight of the strip after it
                after = jnp.concatenate([dpre[0:8], dpre, ahead_ref[:, at]],
                                        axis=0)
                ahead_ref[:, at] = dpre[0:8]
                din = _mixed([_shifted(after, 1 - k + t, _CONV_STRIP)
                              for t in range(k)], w)
                out_ref[0, pl.ds(r, _CONV_STRIP), at] = \
                    din.astype(out_ref.dtype)
            return 0
        lax.fori_loop(0, strips, strip, 0)

    def of_x(r, at):            # the scan's dx and the skip's, D * dy
        return _f32(dx_ref[0, pl.ds(r, _CONV_STRIP), at]) \
            + dspread_ref[:, at] * _f32(dy_ref[0, pl.ds(r, _CONV_STRIP), at])

    pl.when(j < geo.nx)(lambda: run(of_x))
    pl.when(j == geo.nx)(lambda: run(
        lambda r, at: _f32(db_ref[0, pl.ds(r, _CONV_STRIP), at])))
    pl.when(j == geo.nx + 1)(lambda: run(
        lambda r, at: _f32(dc_ref[0, pl.ds(r, _CONV_STRIP), at])))


def _conv_specs(geo, tl, nt, flip):
    """(x, halo, taps, bias) specs of pass A over the grid (column tiles,
    sequences, row tiles); ``flip`` runs the row tiles last to first."""
    tc, lead = geo.tc, geo.nx       # xBC starts d_inner = nx tiles in
    per = tl // _HALO
    row = (lambda i: nt - 1 - i) if flip else (lambda i: i)
    return [
        pl.BlockSpec((1, tl, tc), lambda j, b, i: (b, row(i), lead + j)),
        pl.BlockSpec((1, _HALO, tc), lambda j, b, i: (
            b, jnp.maximum(row(i) * per - 1, 0), lead + j)),
        pl.BlockSpec((geo.k, tc), lambda j, b, i: (0, j)),
        pl.BlockSpec((1, tc), lambda j, b, i: (0, j)),
    ], row


# Each call is a jit of its own, as the scan's are: the blocks of a model
# make the same calls at the same shapes, and a jit inside a jit is traced
# and lowered once for all of them.

@functools.partial(jax.jit, static_argnums=(3,))
def _conv_call(zxbcdt, w_t, bias, geo):
    """x (B, L, H*P), B and C (B, L, G*N) from ``zxbcdt`` (B, L, d_in_proj),
    the taps ``w_t`` (k, conv_dim) and ``bias`` (1, conv_dim) in float32."""
    bsz, length, _ = zxbcdt.shape
    tc, nx = geo.tc, geo.nx
    tl = geo.rows(length, tc)
    nt = length // tl
    ins, _ = _conv_specs(geo, tl, nt, False)
    last = (bsz - 1, nt - 1)

    # an output's block index moves only while its columns are the grid's:
    # before, it waits at its first block, after, at its last (written)
    def x_at(j, b, i):
        on = j < nx
        return (jnp.where(on, b, last[0]), jnp.where(on, i, last[1]),
                jnp.minimum(j, nx - 1))

    def state_at(tile):
        def at(j, b, i):
            on, done = j == tile, j > tile
            return (jnp.where(on, b, jnp.where(done, last[0], 0)),
                    jnp.where(on, i, jnp.where(done, last[1], 0)), 0)
        return at

    dt = zxbcdt.dtype
    return pl.pallas_call(
        functools.partial(_conv_kernel, geo=geo),
        out_shape=(jax.ShapeDtypeStruct((bsz, length, geo.d_inner), dt),
                   jax.ShapeDtypeStruct((bsz, length, tc), dt),
                   jax.ShapeDtypeStruct((bsz, length, tc), dt)),
        grid=(nx + 2, bsz, nt),
        in_specs=ins,
        out_specs=(pl.BlockSpec((1, tl, tc), x_at),
                   pl.BlockSpec((1, tl, tc), state_at(nx)),
                   pl.BlockSpec((1, tl, tc), state_at(nx + 1))),
        scratch_shapes=[pltpu.VMEM((_HALO + tl, tc), dt)],
        compiler_params=_PARAMS,
        interpret=geo.interpret,
        name="mamba_local_conv",
    )(zxbcdt, zxbcdt, w_t, bias)


@functools.partial(jax.jit, static_argnums=(9,))
def _conv_bwd_call(zxbcdt, w_t, bias, dspread, dx, dy, db, dc, wide, geo):
    """``wide`` (B, L, d_in_proj) with the convolution's input cotangent
    written into its ``conv_dim`` columns, and (k + 1, 8, conv_dim) float32
    partial sums: the taps' cotangents, then the bias'."""
    bsz, length, _ = zxbcdt.shape
    tc, nx, k = geo.tc, geo.nx, geo.k
    tl = geo.rows(length, tc)
    nt = length // tl
    ins, row = _conv_specs(geo, tl, nt, True)

    # a cotangent's block moves only while its columns are the grid's
    def x_at(j, b, i):
        on = j < nx
        return (jnp.where(on, b, 0), jnp.where(on, row(i), 0),
                jnp.minimum(j, nx - 1))

    def state_at(tile):
        def at(j, b, i):
            on = j == tile
            return jnp.where(on, b, 0), jnp.where(on, row(i), 0), 0
        return at

    block = (1, tl, tc)
    return pl.pallas_call(
        functools.partial(_conv_bwd_kernel, geo=geo),
        out_shape=(jax.ShapeDtypeStruct(wide.shape, wide.dtype),
                   jax.ShapeDtypeStruct((1, k + 1, 8, geo.conv_dim),
                                        jnp.float32)),
        grid=(nx + 2, bsz, nt),
        in_specs=ins + [
            pl.BlockSpec((1, tc), lambda j, b, i: (0, jnp.minimum(j, nx - 1))),
            pl.BlockSpec(block, x_at), pl.BlockSpec(block, x_at),
            pl.BlockSpec(block, state_at(nx)),
            pl.BlockSpec(block, state_at(nx + 1)),
            pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(pl.BlockSpec(block, lambda j, b, i: (b, row(i), nx + j)),
                   pl.BlockSpec((1, k + 1, 8, tc),
                                lambda j, b, i: (0, 0, 0, j))),
        scratch_shapes=[pltpu.VMEM((_HALO + tl, tc), zxbcdt.dtype),
                        pltpu.VMEM((8, tc), jnp.float32)],
        input_output_aliases={9: 0},
        compiler_params=_PARAMS,
        interpret=geo.interpret,
        name="mamba_local_conv_bwd",
    )(zxbcdt, zxbcdt, w_t, bias, dspread, dx, dy, db, dc, wide)


# ------------------------------------------------------------------- pass B
#
# A grid cell is (column tile j of whole norm groups, sequence b, row tile
# i). With t = y + D x, u = t * silu(z), r = rsqrt(mean_group(u^2) + eps):
# out = round(u r) * w.

def _gated(y_ref, x_ref, z_ref, dspread_ref, r, at):
    rows = pl.ds(r, _STRIP)
    z = _f32(z_ref[0, rows, at])
    sig = _sigmoid(z)
    t = _f32(y_ref[0, rows, at]) + dspread_ref[:, at] * _f32(x_ref[0, rows, at])
    return t, z, sig


def _groups(geo, width):
    return [slice(c, c + geo.gw) for c in range(0, width, geo.gw)]


def _gate_kernel(y_ref, x_ref, z_ref, dspread_ref, w_ref, out_ref, *, geo):
    strips = y_ref.shape[1] // _STRIP

    def strip(s, _):
        r = pl.multiple_of(s * _STRIP, _STRIP)
        for at in _groups(geo, y_ref.shape[2]):
            t, z, sig = _gated(y_ref, x_ref, z_ref, dspread_ref, r, at)
            u = t * (z * sig)
            rs = lax.rsqrt(jnp.mean(u * u, axis=1, keepdims=True) + geo.eps)
            normed = (u * rs).astype(out_ref.dtype)
            out_ref[0, pl.ds(r, _STRIP), at] = \
                (_f32(normed) * w_ref[:, at]).astype(out_ref.dtype)
        return 0
    lax.fori_loop(0, strips, strip, 0)


def _gate_bwd_kernel(y_ref, x_ref, z_ref, dspread_ref, w_ref, dout_ref,
                     dy_ref, wide_ref, sums_ref, *, geo):
    b, i = pl.program_id(1), pl.program_id(2)
    strips = y_ref.shape[1] // _STRIP
    dtype = dy_ref.dtype

    @pl.when((b == 0) & (i == 0))
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    def strip(s, _):
        r = pl.multiple_of(s * _STRIP, _STRIP)
        rows = pl.ds(r, _STRIP)
        for at in _groups(geo, y_ref.shape[2]):
            t, z, sig = _gated(y_ref, x_ref, z_ref, dspread_ref, r, at)
            gate = z * sig
            u = t * gate
            rs = lax.rsqrt(jnp.mean(u * u, axis=1, keepdims=True) + geo.eps)
            dout = _f32(dout_ref[0, rows, at])
            normed = (u * rs).astype(dtype)
            sums_ref[0, 0, :, at] += _fold(dout * _f32(normed))
            dn = dout * w_ref[:, at]
            # d u of u * rsqrt(mean(u^2) + eps)
            du = rs * (dn - (u * rs) * jnp.mean(dn * (u * rs), axis=1,
                                                keepdims=True))
            dt = du * gate
            dy = dt.astype(dtype)
            dy_ref[0, rows, at] = dy
            # the skip's d D, as its d x, from the ROUNDED dy
            sums_ref[0, 1, :, at] += _fold(_f32(dy) * _f32(x_ref[0, rows, at]))
            wide_ref[0, rows, at] = \
                (du * t * (sig * (1.0 + z * (1.0 - sig)))).astype(
                    wide_ref.dtype)
        return 0
    lax.fori_loop(0, strips, strip, 0)


def _gate_specs(geo, tl):
    tg = geo.tg
    tile = pl.BlockSpec((1, tl, tg), lambda j, b, i: (b, i, j))
    row = pl.BlockSpec((1, tg), lambda j, b, i: (0, j))
    return tile, row


@functools.partial(jax.jit, static_argnums=(5,))
def _gate_call(y, x, zxbcdt, dspread, w, geo):
    """The normed, weighted output (B, L, d_inner) from the scan's ``y``,
    ``x``, the z columns of ``zxbcdt``, D spread over its heads' lanes and
    the norm's weight, both (1, d_inner) float32."""
    bsz, length, d_inner = y.shape
    tl = geo.rows(length, geo.tg)
    tile, row = _gate_specs(geo, tl)
    return pl.pallas_call(
        functools.partial(_gate_kernel, geo=geo),
        out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
        grid=(d_inner // geo.tg, bsz, length // tl),
        in_specs=[tile, tile, tile, row, row],
        out_specs=tile,
        compiler_params=_PARAMS,
        interpret=geo.interpret,
        name="mamba_local_gate",
    )(y, x, zxbcdt, dspread, w)


@functools.partial(jax.jit, static_argnums=(6,))
def _gate_bwd_call(y, x, zxbcdt, dspread, w, dout, geo):
    """``dy`` (B, L, d_inner); a NEW (B, L, d_in_proj) buffer with ``dz`` in
    its first ``d_inner`` columns and the rest unwritten (pass A's backward
    and ``d dt`` fill them); (2, 8, d_inner) float32 partial sums: the
    norm weight's cotangent, then ``dy * x`` (d D before its sum over a
    head's lanes)."""
    bsz, length, d_inner = y.shape
    tl = geo.rows(length, geo.tg)
    tile, row = _gate_specs(geo, tl)
    return pl.pallas_call(
        functools.partial(_gate_bwd_kernel, geo=geo),
        out_shape=(jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(zxbcdt.shape, zxbcdt.dtype),
                   jax.ShapeDtypeStruct((1, 2, 8, d_inner), jnp.float32)),
        grid=(d_inner // geo.tg, bsz, length // tl),
        in_specs=[tile, tile, tile, row, row, tile],
        out_specs=(tile, tile,
                   pl.BlockSpec((1, 2, 8, geo.tg),
                                lambda j, b, i: (0, 0, 0, j))),
        compiler_params=_PARAMS,
        interpret=geo.interpret,
        name="mamba_local_gate_bwd",
    )(y, x, zxbcdt, dspread, w, dout)


# ------------------------------------------------------------ the whole of it

def _operands(conv_weight, conv_bias, d, norm_weight, geo):
    f32 = jnp.float32
    return (conv_weight.astype(f32).T, conv_bias.astype(f32)[None],
            jnp.repeat(d.astype(f32), geo.p)[None],
            norm_weight.astype(f32)[None])


def _steps(dt_raw, dt_bias, a_log):
    """The (tokens, heads)-sized part, XLA's: the step sizes and ``a``."""
    f32 = jnp.float32
    return (jax.nn.softplus(dt_raw.astype(f32) + dt_bias.astype(f32)),
            -jnp.exp(a_log.astype(f32)))


def _scan(geo, x, dt, a, b, c):
    bsz, length, _ = x.shape
    y = ssd_scan(x.reshape(bsz, length, geo.h, geo.p), dt, a,
                 b.reshape(bsz, length, geo.g, geo.n),
                 c.reshape(bsz, length, geo.g, geo.n), geo.chunk)
    return y.reshape(bsz, length, geo.d_inner)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _around_scan(zxbcdt, conv_weight, conv_bias, dt_bias, a_log, d,
                 norm_weight, geo):
    w_t, bias, dspread, nw = _operands(conv_weight, conv_bias, d,
                                       norm_weight, geo)
    x, b, c = _conv_call(zxbcdt, w_t, bias, geo)
    dt, a = _steps(zxbcdt[..., geo.d_inner + geo.conv_dim:], dt_bias, a_log)
    y = _scan(geo, x, dt, a, b, c)
    return _gate_call(y, x, zxbcdt, dspread, nw, geo)


def _around_scan_fwd(zxbcdt, conv_weight, conv_bias, dt_bias, a_log, d,
                     norm_weight, geo):
    w_t, bias, dspread, nw = _operands(conv_weight, conv_bias, d,
                                       norm_weight, geo)
    x, b, c = _conv_call(zxbcdt, w_t, bias, geo)
    (dt, a), steps_back = jax.vjp(
        _steps, zxbcdt[..., geo.d_inner + geo.conv_dim:], dt_bias, a_log)
    y, scan_back = jax.vjp(functools.partial(_scan, geo), x, dt, a, b, c)
    out = _gate_call(y, x, zxbcdt, dspread, nw, geo)
    return out, (zxbcdt, conv_weight, conv_bias, d, norm_weight, x, y,
                 steps_back, scan_back)


@under_scope("mamba_local")
def _around_scan_bwd(geo, res, dout):
    (zxbcdt, conv_weight, conv_bias, d, norm_weight, x, y, steps_back,
     scan_back) = res
    w_t, bias, dspread, nw = _operands(conv_weight, conv_bias, d,
                                       norm_weight, geo)
    dy, wide, sums = _gate_bwd_call(y, x, zxbcdt, dspread, nw, dout, geo)
    dx, ddt, da, db, dc = scan_back(dy)
    wide, taps = _conv_bwd_call(zxbcdt, w_t, bias, dspread, dx, dy, db, dc,
                                wide, geo)
    ddt_raw, ddt_bias, da_log = steps_back((ddt, da))
    wide = lax.dynamic_update_slice_in_dim(
        wide, ddt_raw.astype(wide.dtype), geo.d_inner + geo.conv_dim, axis=2)
    sums = jnp.sum(sums[0], axis=1)                     # (2, d_inner)
    taps = jnp.sum(taps[0], axis=1)                     # (k + 1, conv_dim)
    return (wide, taps[:geo.k].T.astype(conv_weight.dtype),
            taps[geo.k].astype(conv_bias.dtype), ddt_bias, da_log,
            jnp.sum(sums[1].reshape(geo.h, geo.p), axis=1).astype(d.dtype),
            sums[0].astype(norm_weight.dtype))


_around_scan.defvjp(_around_scan_fwd, _around_scan_bwd)


def around_scan(zxbcdt, conv_weight, conv_bias, dt_bias, a_log, d,
                norm_weight, *, head_dim, n_groups, state_size, chunk, eps,
                interpret=None):
    """``nn.Mamba2`` between its projections, kernel form: ``zxbcdt``
    (B, L, d_inner + conv_dim + H) -> (B, L, d_inner), the scan inside.
    Shapes as ``takes_kernel`` admits; ``interpret`` (tests on a CPU) runs
    the kernels in Pallas' interpreter."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    geo = _Geo(d.shape[0], head_dim, n_groups, state_size,
               conv_weight.shape[1], chunk, float(eps), bool(interpret))
    return _around_scan(zxbcdt, conv_weight, conv_bias, dt_bias, a_log, d,
                        norm_weight, geo)
