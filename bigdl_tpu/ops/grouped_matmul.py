"""Grouped matrix products over rows sorted by group (an expert's run).

``R`` rows lie sorted by group, group ``e``'s run ``counts[e]`` long and
the runs back to back from row 0; what lies past the last run is not read
into any result (it may hold anything, NaNs too). Two Pallas (Mosaic)
kernels, both over row tiles of ``ROW_TILE`` rows that pad no run: a tile
that straddles two runs is visited once for each, and a visit multiplies
only the ``SUB_ROWS``-row parts of the tile that hold rows of its group,
under a row mask; the visits are at most ``ceil(landed / tile) + E - 1``
(``_visits``) and the grid's bound is the number of visits that hold real
rows, a traced value: tiles past the last run cost nothing.

- ``grouped_products`` (calls named ``moe_gmm*``): for every visit, a list
  of products ``lhs[rows] @ rhs[e]`` (or ``@ rhs[e]^T``, the right-hand
  side read as it lies) of float32 accumulation over the WHOLE contraction,
  handed to an ``epilogue`` that the caller writes in ``jax.numpy`` (an
  activation, its derivative, a pick weight: the kernel knows products and
  no family), whose results are stored under the row mask. The output
  columns are the grid's OUTER axis and the visits its inner one, so a
  group's matrix tile stays in VMEM over that group's consecutive row
  tiles and is read from HBM once a pass. With ``add_to`` the first result
  is not stored by row but ADDED to the rows an index names (the picks'
  tokens): that (T, tn) float32 tile stays in VMEM over all visits, the
  rows are added one by one, and it is written once.
- ``grouped_transposed`` (``moe_gmm_t*``): ``lhs[rows]^T @ rhs[rows]`` ->
  (E, K, N), the weight gradients. The (tk, tn) float32 accumulator lives
  in VMEM over ALL row tiles of a group and is written ONCE a group, in the
  output's dtype (zeros for a group with no rows): no read-modify-write in
  HBM, no zero-fill, no cast afterwards.

``grouped_matmul`` is the plain differentiable product built from the two
(its gradients are the transposed right-hand side and the transposed
product); ``parallel/expert.py`` fuses its layers' stages through the
``epilogue``. Off a TPU the kernels run in Pallas' interpreter
(``interpret``), which is how tier-1 holds them. A width that is no
multiple of 128 lanes is taken where it is a CONTRACTION's whole length or
an output's whole width (the block is then the array's full dimension);
output columns are tiled only where 128 divides them. Measured: PERF.md
section 6, PR 35.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.scopes import under_scope

_LANES = 128
_NN = (((1,), (0,)), ((), ()))          # a (M, K) x b (K, N) -> (M, N)
_NT = (((1,), (1,)), ((), ()))          # a (M, K) x b (N, K) -> (M, N)
_TN = (((0,), (0,)), ((), ()))          # a (K, M) x b (K, N) -> (M, N)

#: rows of one tile: one grid step, one fetch of its rows. Measured on the
#: v5e at the Trinity cell's shapes, forward + backward of a layer: 512 rows
#: with parts of 128 4.73 ms, 256 / 128 4.83, 256 / 256 5.01, 512 / 64
#: 5.20, 128 / 128 5.31 (PERF.md section 6, PR 35)
ROW_TILE = 512
#: rows of the parts of a tile: a visit multiplies only the parts that hold
#: rows of its group (a run's first and last tile are shared with its
#: neighbours); a part is one pass of the MXU's 128 rows
SUB_ROWS = 128
#: what the blocks of one call may hold in VMEM, double-buffered (the v5e
#: has 128 MiB; Mosaic's default scope is 16)
_VMEM_BLOCKS = 40 << 20
_VMEM_LIMIT = 96 << 20
#: entries of one SMEM block of row indices (XLA tiles a 1-D int32 array by
#: 1,024): longer index arrays are whole blocks
INDEX_BLOCK = 1024


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _visits(counts, n_tiles, tm, empty):
    """The visit table of ``n_tiles`` row tiles of ``tm``: (offsets (E+1,),
    group (V,), tile (V,)) and how many visits hold work. A group is
    visited once for every tile its run touches; with ``empty`` a group
    without rows is visited once too (its result is zeros)."""
    e = counts.shape[0]
    counts = counts.astype(jnp.int32)
    ends = jnp.cumsum(counts)
    first = (ends - counts) // tm
    per = jnp.where(counts > 0, (ends - 1) // tm - first + 1,
                    1 if empty else 0).astype(jnp.int32)
    last = jnp.cumsum(per)
    v = jnp.arange(n_tiles + e, dtype=jnp.int32)
    group = jnp.minimum(jnp.sum(v[:, None] >= last[None, :], axis=1),
                        e - 1).astype(jnp.int32)
    tile = jnp.clip(first[group] + v - (last[group] - per[group]), 0,
                    n_tiles - 1).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (offsets, group, tile), last[-1]


def _each_part(lo, hi, tm, sub, body):
    """``body(part, own)`` for every ``sub``-row part of a tile that holds
    rows of the visit's group, rows [lo, hi) of the tile: ``part`` the
    part's rows as a slice, ``own`` (sub, 1) which of them are the
    group's. A loop in the kernel, not a copy of its body a part: as fast
    on the chip, a third of the kernel's compile time, and a warm start
    pays by the kernel's size (PERF.md section 6, PR 35)."""
    def step(i, carry):
        at = pl.multiple_of(i * sub, sub)

        @pl.when((lo < at + sub) & (hi > at))
        def _():
            row = at + _iota((sub, 1), 0)
            body(pl.ds(at, sub), (row >= lo) & (row < hi))

        return carry

    lax.fori_loop(0, tm // sub, step, None)


def _dot(a, b, dims, widen):
    # Pallas' interpreter runs on XLA's CPU backend, which has no bf16
    # product with a transposed operand: there the operands are widened
    # first (exact: a product of two bf16 values fits float32)
    if widen:
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _params(block_bytes, axes):
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",) * axes,
        vmem_limit_bytes=int(min(_VMEM_LIMIT,
                                 max(32 << 20, 2 * block_bytes + (16 << 20)))))


def _col_tile(n, per_col_bytes):
    """Columns a step: the widest multiple of 128 up to 1024 that divides
    ``n`` and keeps the double-buffered blocks inside ``_VMEM_BLOCKS``; all
    of ``n`` where 128 does not divide it."""
    if n % _LANES:
        return n
    return next(tn for tn in range(1024, 0, -_LANES)
                if n % tn == 0 and (2 * tn * per_col_bytes <= _VMEM_BLOCKS
                                    or tn == _LANES))


# ------------------------------------------------- products over sorted rows

def _products_kernel(offs, group, tile, *refs, tm, sub, sides, n_lhs,
                     n_cols, n_out, row_sum, added, epilogue, widen):
    v = pl.program_id(1)
    lhs = refs[:n_lhs]
    rhs = refs[n_lhs:n_lhs + len(sides)]
    cols = refs[n_lhs + len(sides):n_lhs + len(sides) + n_cols]
    outs = refs[n_lhs + len(sides) + n_cols + added:]
    # the group's rows inside this tile: [lo, hi) of [0, tm)
    lo = offs[group[v]] - tile[v] * tm
    hi = offs[group[v] + 1] - tile[v] * tm
    if added:
        to_row = refs[n_lhs + len(sides) + n_cols]
        total, rows = outs[-2:]

        @pl.when(v == 0)
        def _():
            total[...] = jnp.zeros_like(total)

    def part_of_tile(part, own):
        prods = [_dot(lhs[i][part, :], r[...], _NT if t else _NN, widen)
                 for (i, t), r in zip(sides, rhs)]
        res = epilogue(prods, [c[part, :] for c in cols])
        if added:
            # rows of other groups and past the last run add zero to the
            # row their entry of ``to_row`` names
            rows[part, :] = jnp.where(own, res[0].astype(jnp.float32), 0.0)
            res = res[1:]
        for o, val in zip(outs[:n_out], res):
            o[part, :] = jnp.where(own, val.astype(o.dtype), o[part, :])
        if row_sum:
            o = outs[n_out]
            o[part, :] = jnp.where(
                own, jnp.sum(res[n_out], axis=1, keepdims=True), o[part, :])

    _each_part(lo, hi, tm, sub, part_of_tile)

    if added:
        # ``to_row`` comes in blocks of INDEX_BLOCK entries (XLA's tiling
        # of a 1-D int32 array), of which this tile's begin at ``base``
        base = (tile[v] * tm) % to_row.shape[0]

        def eight(i, _):
            for r in range(8):
                at = pl.ds(to_row[base + i * 8 + r], 1)
                total[at, :] = total[at, :] + rows[pl.ds(i * 8 + r, 1), :]
            return _

        lax.fori_loop(jnp.clip(lo, 0, tm) // 8,
                      (jnp.clip(hi, 0, tm) + 7) // 8, eight, None)


def grouped_products(counts, lhs, pairs, epilogue, out_dtypes, cols=(),
                     row_sum=False, add_to=None, name="moe_gmm",
                     interpret=None):
    """For the rows of every group ``e``: ``epilogue(prods, cols)`` stored,
    where ``prods[p]`` is ``lhs[i][rows] @ rhs[e]`` for ``pairs[p] = (i,
    rhs, transposed)`` (``rhs`` (E, K, N), or (E, N, K) when transposed)
    over the whole of K in float32, a (tm, tn) tile each, and ``cols`` are
    (R, 1) float32 columns (tm, 1). The epilogue returns one (tm, tn) value
    for each of ``out_dtypes`` -> (R, N) arrays; with ``row_sum`` one more,
    float32, whose sums over each row's N columns come back as (R,)
    float32. Rows past the last run are written nowhere: what the outputs
    hold there is not defined.

    ``add_to = (to_row (R,) int32, T)``: the epilogue's FIRST value is not
    stored by row; row r of it is added, in float32, to row ``to_row[r]``
    of a (T, N) float32 result that comes back first (zeros where nothing
    is added). That result's (T, tn) tile stays in VMEM over all visits and
    is written once; the rows are added one by one.

    The call is a jit of its own, as ``ops/ssd_scan.py``'s: the expert
    blocks of a model make the same calls at the same shapes, and with an
    ``epilogue`` that is equal by value (a module-level function, a frozen
    dataclass) they share one trace, one lowering and one Mosaic kernel in
    the program, which a warm start pays for by the kernel."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    to_row, total_rows = add_to if add_to is not None else (None, None)
    return _products_call(
        counts, tuple(lhs), tuple(w for _, w, _ in pairs), tuple(cols),
        to_row, sides=tuple((i, t) for i, _, t in pairs), epilogue=epilogue,
        out_dtypes=tuple(jnp.dtype(d) for d in out_dtypes), row_sum=row_sum,
        total_rows=total_rows, name=name, interpret=interpret,
        tm=min(ROW_TILE, lhs[0].shape[0]), sub=SUB_ROWS)


@functools.partial(jax.jit, static_argnames=(
    "sides", "epilogue", "out_dtypes", "row_sum", "total_rows", "name",
    "interpret", "tm", "sub"))
def _products_call(counts, lhs, rhs, cols, to_row, **static):
    return _products(counts, lhs, rhs, cols, to_row, **static)


def _products(counts, lhs, rhs, cols, to_row, *, sides, epilogue, out_dtypes,
              row_sum, total_rows, name, interpret, tm, sub):
    r = lhs[0].shape[0]
    assert r % tm == 0 and all(a.shape[0] == r for a in lhs), (r, tm)
    n = rhs[0].shape[1 if sides[0][1] else 2]
    per_col = sum(w.shape[2 if t else 1] * w.dtype.itemsize
                  for w, (_, t) in zip(rhs, sides))
    if to_row is not None:
        per_col += 4 * total_rows
    tn = _col_tile(n, per_col)
    meta, n_visits = _visits(counts, r // tm, tm, empty=False)

    def at_rows(j, v, offs, group, tile):
        return tile[v], 0

    in_specs = [pl.BlockSpec((tm, a.shape[1]), at_rows) for a in lhs]
    for w, (_, t) in zip(rhs, sides):
        if t:
            in_specs.append(pl.BlockSpec(
                (None, tn, w.shape[2]),
                lambda j, v, offs, group, tile: (group[v], j, 0)))
        else:
            in_specs.append(pl.BlockSpec(
                (None, w.shape[1], tn),
                lambda j, v, offs, group, tile: (group[v], 0, j)))
    in_specs += [pl.BlockSpec((tm, 1), at_rows) for _ in cols]
    ins = [*lhs, *rhs, *cols]
    out_shape = [jax.ShapeDtypeStruct((r, n), d) for d in out_dtypes]
    out_specs = [pl.BlockSpec((tm, tn),
                              lambda j, v, offs, group, tile: (tile[v], j))
                 for _ in out_dtypes]
    if row_sum:
        out_shape.append(jax.ShapeDtypeStruct((n // tn, r, 1), jnp.float32))
        out_specs.append(pl.BlockSpec(
            (None, tm, 1), lambda j, v, offs, group, tile: (j, tile[v], 0)))
    scratch = []
    if to_row is not None:
        ins.append(to_row)
        per = (INDEX_BLOCK if r > INDEX_BLOCK else r) // tm
        assert r % (per * tm) == 0, (r, tm)
        in_specs.append(pl.BlockSpec(
            (per * tm,), lambda j, v, offs, group, tile: (tile[v] // per,),
            memory_space=pltpu.SMEM))
        out_shape.append(jax.ShapeDtypeStruct((total_rows, n), jnp.float32))
        out_specs.append(pl.BlockSpec(
            (total_rows, tn), lambda j, v, offs, group, tile: (0, j)))
        scratch.append(pltpu.VMEM((tm, tn), jnp.float32))
    blocks = (sum(tm * a.shape[1] * a.dtype.itemsize for a in lhs)
              + tn * per_col + (len(out_dtypes) + 2) * tm * tn * 4)
    res = list(pl.pallas_call(
        functools.partial(
            _products_kernel, tm=tm, sub=sub if tm % sub == 0 else tm,
            sides=sides, n_lhs=len(lhs), n_cols=len(cols),
            n_out=len(out_dtypes), row_sum=row_sum,
            added=to_row is not None, epilogue=epilogue, widen=interpret),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            # a first visit there always is: it zeroes the added result
            grid=(n // tn, jnp.maximum(n_visits, int(to_row is not None))),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        compiler_params=_params(blocks, 2),
        interpret=interpret, name=name,
    )(*meta, *ins))
    if to_row is not None:
        res.insert(0, res.pop())
    if row_sum:
        res[-1] = jnp.sum(res[-1][:, :, 0], axis=0)
    return res


# ------------------------------------------ the transposed product, by group

def _transposed_kernel(offs, group, tile, *refs, tm, sub, n_rhs, scaled,
                       widen):
    lhs = refs[0]
    rhs = refs[1:1 + n_rhs]
    outs = refs[-2 * n_rhs:-n_rhs]
    accs = refs[-n_rhs:]
    v = pl.program_id(2)
    n_v = pl.num_programs(2)
    e = group[v]
    lo = offs[e] - tile[v] * tm
    hi = offs[e + 1] - tile[v] * tm

    @pl.when((v == 0) | (group[jnp.maximum(v - 1, 0)] != e))
    def _():
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)

    def part_of_tile(part, own):
        a = lhs[part, :]
        a = jnp.where(own, a, jnp.zeros_like(a))
        for b_ref, acc in zip(rhs, accs):
            b = b_ref[part, :]
            if scaled:
                b = (b.astype(jnp.float32)
                     * refs[1 + n_rhs][part, :]).astype(b.dtype)
            acc[...] += _dot(a, jnp.where(own, b, jnp.zeros_like(b)), _TN,
                             widen)

    _each_part(lo, hi, tm, sub, part_of_tile)

    @pl.when((v == n_v - 1) | (group[jnp.minimum(v + 1, n_v - 1)] != e))
    def _():
        for out, acc in zip(outs, accs):
            out[...] = acc[...].astype(out.dtype)


def grouped_transposed(counts, lhs, rhs, out_dtype, scale=None,
                       name="moe_gmm_t", interpret=None):
    """``lhs[rows of e]^T @ b[rows of e]`` for every group and every ``b``
    of the list ``rhs`` -> a list of (E, K, N) in ``out_dtype``, summed in
    float32 over all of the group's rows before the one rounding.
    ``scale`` (R, 1) float32 multiplies the right-hand sides' rows first
    (in float32, rounded back to their dtype). A jit of its own, as
    ``grouped_products``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _transposed_call(
        counts, lhs, tuple(rhs), scale, out_dtype=jnp.dtype(out_dtype),
        name=name, interpret=interpret, tm=min(ROW_TILE, lhs.shape[0]),
        sub=SUB_ROWS)


@functools.partial(jax.jit, static_argnames=(
    "out_dtype", "name", "interpret", "tm", "sub"))
def _transposed_call(counts, lhs, rhs, scale, **static):
    return _transposed(counts, lhs, rhs, scale, **static)


def _transposed(counts, lhs, rhs, scale, *, out_dtype, name, interpret, tm,
                sub):
    r, k = lhs.shape
    n = rhs[0].shape[1]
    assert r % tm == 0 and all(b.shape == (r, n) for b in rhs), (r, tm)
    tk = _col_tile(k, 0)
    tn = _col_tile(n, 0)
    meta, n_visits = _visits(counts, r // tm, tm, empty=True)
    ins = [lhs, *rhs] + ([scale] if scale is not None else [])
    in_specs = [pl.BlockSpec((tm, tk), lambda i, j, v, offs, group, tile:
                             (tile[v], i))]
    in_specs += [pl.BlockSpec((tm, tn), lambda i, j, v, offs, group, tile:
                              (tile[v], j)) for _ in rhs]
    if scale is not None:
        in_specs.append(pl.BlockSpec(
            (tm, 1), lambda i, j, v, offs, group, tile: (tile[v], 0)))
    blocks = (tm * (tk * lhs.dtype.itemsize
                    + len(rhs) * tn * rhs[0].dtype.itemsize)
              + len(rhs) * tk * tn * (4 + out_dtype.itemsize))
    return pl.pallas_call(
        functools.partial(_transposed_kernel, tm=tm,
                          sub=sub if tm % sub == 0 else tm, n_rhs=len(rhs),
                          scaled=scale is not None, widen=interpret),
        out_shape=[jax.ShapeDtypeStruct((counts.shape[0], k, n), out_dtype)
                   for _ in rhs],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(k // tk, n // tn, n_visits),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec(
                (None, tk, tn),
                lambda i, j, v, offs, group, tile: (group[v], i, j))
                for _ in rhs],
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32) for _ in rhs]),
        compiler_params=_params(blocks, 3),
        interpret=interpret, name=name,
    )(*meta, *ins)


# --------------------------------------------- the plain grouped product

def _first(prods, cols):
    return prods


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
@under_scope("moe_experts")
def grouped_matmul(lhs, rhs, counts, transpose_rhs=False, interpret=None):
    """``lhs[rows of e] @ rhs[e]`` (``@ rhs[e]^T`` with ``transpose_rhs``,
    ``rhs`` then (E, N, K)) -> (R, N) in ``lhs``'s dtype, zeros past the
    last run. Differentiable in both operands."""
    out, = grouped_products(counts, [lhs], [(0, rhs, transpose_rhs)],
                            _first, [lhs.dtype], interpret=interpret)
    landed = jnp.sum(counts)
    return jnp.where(jnp.arange(lhs.shape[0])[:, None] < landed, out, 0)


def _grouped_matmul_fwd(lhs, rhs, counts, transpose_rhs, interpret):
    return (grouped_matmul(lhs, rhs, counts, transpose_rhs, interpret),
            (lhs, rhs, counts))


@under_scope("moe_experts")
def _grouped_matmul_bwd(transpose_rhs, interpret, res, dout):
    lhs, rhs, counts = res
    dout = dout.astype(lhs.dtype)
    dlhs = grouped_matmul(dout, rhs, counts, not transpose_rhs, interpret)
    a, b = (dout, lhs) if transpose_rhs else (lhs, dout)
    return (dlhs, grouped_transposed(counts, a, [b], rhs.dtype,
                                     interpret=interpret)[0], None)


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)
