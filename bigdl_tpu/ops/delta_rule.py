"""The gated delta rule of the linear-attention mixers (Gated DeltaNet, Yang
et al., arXiv:2412.06464) in its chunked WY form.

The recurrence of one head, a state ``S`` of ``d_k x d_v`` in float32 that
starts at zero::

    S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                         alpha_t = exp(g_t), g_t <= 0

Where Mamba-2's recurrence (``ops/ssd_scan.py``) ADDS a rank-1 term to a
decayed state, this one first multiplies the state by ``I - beta k k^T``:
it takes out what the state already holds under the key before writing the
new value. Inside a chunk of ``C`` positions that is a triangular system
and no cumulative sum. With ``gc`` the log-decay cumulated from the chunk's
start, ``Gamma_ij = exp(gc_i - gc_j)`` and

    A = strict_lower(diag(beta) (K K^T * Gamma))            (C, C)
    T = (I + A)^-1
    W = T (diag(beta exp(gc)) K)      U = T (diag(beta) V)  (``_wy``)

a chunk that starts from the state ``S`` computes

    V' = U - W S
    O  = (diag(exp(gc)) Q) S + lower(Q K^T * Gamma) V'
    S' = exp(gc_C) S + (diag(exp(gc_C - gc)) K)^T V'

Two forms of the one algorithm, chosen by what ``gated_delta_rule`` can see
(``takes_kernel``: the backend, the dtypes, the chunk and the head sizes)
and by nothing else:

- ``form="kernel"``: on a TPU for bf16 operands at a chunk of 64 and heads
  Mosaic tiles (the published 96 / 192, held 15 or 30 at a time). Two
  Pallas (Mosaic) calls behind one ``jax.custom_vjp``, a grid cell a
  (batch, head, group of chunks) with the chunks of a (batch, head) along
  the LAST grid axis, which runs in order, and the state ``S`` (d_k, d_v)
  float32 in VMEM scratch from a zero start. ``delta_rule_fwd`` does the
  three lines above a chunk, ``Gamma``, ``A``, ``T``, ``W``, ``U`` and
  ``V'`` made and used in VMEM; the state is updated THROUGH ``V'``, so the
  chunk's linear step ``M`` below is never formed. ``T`` comes from a
  substitution on the VPU (``_solve``), exact float32 without a product,
  two chunks' systems side by side on a vreg's lanes (they wait for no
  state).
  ``delta_rule_bwd`` takes the chunks last to first carrying ``dS``: a
  chunk rebuilds everything from its inputs and the state it started from,
  then ``dV' = P^T dO + K~ dS'``, ``dS = (diag(exp gc) Q)^T dO + exp(gc_C)
  dS' - W^T dV'``, ``dW = -dV' S^T``, ``dT = dW Kb^T + dV' Vb^T``, ``dA =
  -T^T dT T^T`` on the strict lower triangle, and from those ``dq``,
  ``dk``, ``dv``, ``d beta`` and ``d gc`` (every decay is ``exp`` of a
  difference of ``gc``). Written to HBM: ``o``, and by the forward that is
  differentiated each chunk's START state in float32 (141.6 MB a layer at
  1 x 8,192 x 15 heads, alive for one block's backward under block remat):
  the one residual beside the inputs, because it alone cannot be made again
  without the pass before it. NOT written: anything with a (C, C) face,
  ``W``, ``U``, ``V'``, ``M``. XLA keeps what is (tokens, heads)-sized:
  ``gc`` (a chunk's cumulative sum, and its transpose, a reverse one, on
  the way back) and the (B, L, H, d) <-> (B, H, L, d) transposes that take
  a head of 96 out of the token's 128-lane tiles, inside the scope.
- ``form="chunked"``: everything else (a CPU, tier-1's heads of 8 and
  chunks of 16, float32 operands): plain ``jax.numpy`` that XLA compiles
  and autodiff differentiates, the kernels' oracle in the tests. The state
  at each chunk's start follows from one LINEAR step a chunk, ``S' = M S +
  add`` with ``M = exp(gc_C) I - K~^T W`` (d_k, d_k) and ``add = K~^T U``,
  both made for every chunk at once; ``_chunk_states`` carries it, one
  (d_k, d_k) x (d_k, d_v) product a chunk and head. ``T`` comes from ``2
  log2 C`` products of (C, C) matrices: the inverses of the diagonal
  blocks, doubled in width a level (``_unit_lower_inverse``).

The controls' seam: a ``correct`` gate plants its fault ``no_delta_term`` by
replacing ``_wy`` BY NAME (``benchmark/builders/olmo_hybrid.planted``), and
``_wy`` takes a (C, C) ``decay`` that the kernel form never builds. So
``gated_delta_rule`` takes the kernel form only while the module's ``_wy``
is the function defined here; a replacement is run, through the XLA form,
and counted as ``chunked``.

Precision, the same in both forms: the products over ``d_k``, ``d_v`` and
``C`` take their operands in ``q``'s dtype (bf16 under the training policy)
and accumulate in float32; the decays stay in log space until a difference
of them is exponentiated (every exponent is <= 0); ``A``, ``T`` and the
carried state are float32, and so is what makes ``T`` (the XLA form's
products, its carry's too, at ``HIGHEST``; the kernel form's substitution
on the VPU, and ``dA``'s two products at Mosaic's float32 precision);
``T``, ``W``, ``U``, ``V'`` and the state's copy for its read-outs are
rounded to the operands' dtype ONCE, before their products. Measured:
PERF.md section 6, PR 46.
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
from bigdl_tpu.ops.ssd_scan import _LANES, _NT, _TN, _iota, _padded

_EXACT = lax.Precision.HIGHEST
_CHUNK = 64         # the chunk the kernel form is written for


def takes_kernel(backend, dtype, gate_dtype, chunk, d_k, d_v) -> bool:
    """The path rule: the Mosaic calls on a TPU for bf16 ``q``, ``k`` and
    ``v`` with float32 ``g`` and ``beta``, a chunk of 64 (eight float32
    vregs a (C, C) tile, two of them side by side on a vreg's lanes in the
    solve) and heads Mosaic tiles, ``d_k`` whole packed bf16 sublane tiles up
    to one lane tile and ``d_v`` whole half lane tiles up to two (the
    published 96 / 192 and 128 / 128; the state is (d_k, d_v) float32 in
    vregs); the XLA form everywhere else."""
    return (backend == "tpu" and dtype == jnp.bfloat16
            and gate_dtype == jnp.float32 and chunk == _CHUNK
            and d_k % 16 == 0 and 32 <= d_k <= _LANES
            and d_v % 64 == 0 and 64 <= d_v <= 2 * _LANES)


def gated_delta_rule(q, k, v, g, beta, chunk: int = 64):
    """``q``, ``k`` (B, L, H, d_k) and ``v`` (B, L, H, d_v) in one dtype,
    ``g`` (B, L, H) the log-decay (<= 0) and ``beta`` (B, L, H) the write
    strength, float32 -> ``o`` (B, L, H, d_v) in ``v``'s dtype. ``q`` and
    ``k`` as the recurrence reads them (normalised and scaled by the
    caller). L need not be a multiple of ``chunk``: the tail is padded with
    ``g = 0`` and ``beta = 0`` (no decay, no write), which leaves the state
    and every real output as they are."""
    from bigdl_tpu.telemetry import get_registry, instruments
    # the controls' seam: a gate plants its fault by replacing ``_wy`` BY
    # NAME, and only the XLA form calls it, so a replaced ``_wy`` is run
    # through that form (and counted as it)
    kernel = _wy is _WY_AS_DEFINED and takes_kernel(
        jax.default_backend(), v.dtype, jnp.result_type(g, beta), chunk,
        q.shape[3], v.shape[3])
    # trace-time count, as bigdl_ssd_scan_total: which form a compiled
    # program holds
    instruments(get_registry()).delta_rule_total.labels(
        form="kernel" if kernel else "chunked").inc()
    with jax.named_scope("delta_rule"):
        if kernel:
            return _delta_kernel(q, k, v, g, beta, chunk)
        return _delta_chunked(q, k, v, g, beta, chunk)


# ------------------------------------------------------------- the XLA form

@jax.custom_vjp
def _unit_lower_inverse(a):
    """``T = (I + a)^-1`` of strictly lower-triangular ``a`` (..., C, C) in
    float32, by doubling: the inverses of the diagonal blocks of width
    ``b`` give those of width ``2b``, ``[[T1, 0], [-T2 a21 T1, T2]]``, which
    with ``T`` block diagonal and ``E`` the blocks ``a21`` alone is ``T <-
    T - T E T``: two (C, C) products a level, ``log2 C`` levels, every one
    an exact step of the substitution (the finite series ``sum_j (-a)^j``
    costs as much and cancels terms of 1e18 where a key repeats through a
    chunk). Its backward is the inverse's own rule, ``da = -T^T dT T^T``:
    two products from ``T`` alone."""
    c = a.shape[-1]
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    inverse = jnp.broadcast_to(jnp.eye(c, dtype=a.dtype), a.shape)
    b = 1
    while b < c:
        merged = (row // (2 * b) == col // (2 * b)) \
            & (row % (2 * b) >= b) & (col % (2 * b) < b)
        inverse = inverse - jnp.matmul(
            inverse, jnp.matmul(jnp.where(merged, a, 0.0), inverse,
                                precision=_EXACT), precision=_EXACT)
        b *= 2
    return inverse


def _unit_lower_inverse_fwd(a):
    t = _unit_lower_inverse(a)
    return t, t


@under_scope("delta_rule")
def _unit_lower_inverse_bwd(t, dt):
    t_t = jnp.swapaxes(t, -1, -2)
    da = -jnp.matmul(t_t, jnp.matmul(dt, t_t, precision=_EXACT),
                     precision=_EXACT)
    # ``a`` is strictly lower triangular: its cotangent lives there alone
    return (jnp.tril(da, -1),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _wy(k, v, gc, beta, decay):
    """The chunk's triangular system solved for every position at once:
    ``W`` (B, nc, C, H, d_k) and ``U`` (B, nc, C, H, d_v), float32, from
    the keys and values (B, nc, C, H, d), the cumulated log-decay and the
    write strength (B, nc, C, H) and ``decay`` = ``Gamma`` on and below the
    diagonal (B, nc, H, C, C)."""
    f32, cd = jnp.float32, k.dtype
    c = k.shape[2]
    kk = jnp.einsum("bnchd,bnshd->bnhcs", k, k, preferred_element_type=f32)
    below = jnp.tril(jnp.ones((c, c), bool), -1)
    a = jnp.where(below, kk * decay
                  * jnp.moveaxis(beta, 2, -1)[..., None], 0.0)
    t = _unit_lower_inverse(a).astype(cd)
    kb = (k.astype(f32) * (beta * jnp.exp(gc))[..., None]).astype(cd)
    vb = (v.astype(f32) * beta[..., None]).astype(cd)
    w = jnp.einsum("bnhcs,bnshd->bnchd", t, kb, preferred_element_type=f32)
    u = jnp.einsum("bnhcs,bnshe->bnche", t, vb, preferred_element_type=f32)
    return w, u


_WY_AS_DEFINED = _wy


def _chunk_states(step, add):
    """Between chunks: the state at each chunk's START, from each chunk's
    linear step ``S' = step @ S + add`` (B, nc, H, d_k, d_k) and (B, nc, H,
    d_k, d_v). Float32, one product a chunk, from a zero state."""
    def carry_on(state, inp):
        m, plus = inp
        return jnp.matmul(m, state, precision=_EXACT) + plus, state

    _, before = lax.scan(
        carry_on, jnp.zeros(add.shape[:1] + add.shape[2:], jnp.float32),
        (jnp.moveaxis(step, 1, 0), jnp.moveaxis(add, 1, 0)))
    return jnp.moveaxis(before, 0, 1)


def _delta_chunked(q, k, v, g, beta, c):
    bsz, length, h, dk = q.shape
    dv = v.shape[-1]
    cd, f32 = v.dtype, jnp.float32
    q, k, v, g, beta = _padded(c, length, q, k, v, g, beta)
    nc = q.shape[1] // c
    q, k = (t.astype(cd).reshape(bsz, nc, c, h, dk) for t in (q, k))
    v = v.reshape(bsz, nc, c, h, dv)
    g, beta = (t.astype(f32).reshape(bsz, nc, c, h) for t in (g, beta))

    gc = jnp.cumsum(g, axis=2)          # log-decay from the chunk's start
    gc_t = jnp.moveaxis(gc, 2, -1)      # (B, nc, H, C)
    # position l reads s <= l at decay exp(gc_l - gc_s)
    seen = jnp.tril(jnp.ones((c, c), bool))
    decay = jnp.exp(jnp.where(seen, gc_t[..., :, None] - gc_t[..., None, :],
                              -jnp.inf))
    w, u = _wy(k, v, gc, beta, decay)

    # each chunk's linear step on the state it starts from
    total = gc[:, :, -1]                                    # (B, nc, H)
    k_end = (k.astype(f32)
             * jnp.exp(total[:, :, None] - gc)[..., None]).astype(cd)
    step = jnp.exp(total)[..., None, None] * jnp.eye(dk, dtype=f32) \
        - jnp.einsum("bnchd,bnchf->bnhdf", k_end, w.astype(cd),
                     preferred_element_type=f32)
    add = jnp.einsum("bnchd,bnche->bnhde", k_end, u.astype(cd),
                     preferred_element_type=f32)
    before = _chunk_states(step, add).astype(cd)        # (B, nc, H, dk, dv)

    v_new = (u - jnp.einsum("bnchd,bnhde->bnche", w.astype(cd), before,
                            preferred_element_type=f32)).astype(cd)
    qk = jnp.einsum("bnchd,bnshd->bnhcs", q, k, preferred_element_type=f32)
    o = jnp.einsum("bnhcs,bnshe->bnche", (qk * decay).astype(cd), v_new,
                   preferred_element_type=f32)
    q_in = (q.astype(f32) * jnp.exp(gc)[..., None]).astype(cd)
    o = o + jnp.einsum("bnchd,bnhde->bnche", q_in, before,
                       preferred_element_type=f32)
    return o.reshape(bsz, nc * c, h, dv)[:, :length].astype(cd)


# ---------------------------------------------------------- the kernel form
#
# What a grid cell (batch, head, group of chunks) sees, the chunks of one
# (batch, head) along the LAST grid axis, which runs in order:
#   q, k, dq, dk   (n C, d_k)   the head's tokens, (B, H, L, d) in HBM: a
#   v, o, do, dv   (n C, d_v)   head of 96 is no multiple of 128 lanes, so
#                               the head leaves the token's lanes in XLA (a
#                               transpose a side, inside the scope)
#   rows, drows    (n, 2, C)    float32, lane-dense a chunk: the log-decay
#                               cumulated from the chunk's start, then beta;
#                               their cotangents leave the same way
#   states         (n, d_k, d_v) float32: each chunk's START state, written
#                               by the forward that is differentiated, read
#                               by the backward
# and in VMEM scratch the carried (d_k, d_v) float32: the state forward, its
# cotangent backward. A chunk's scalars scale ROWS of (C, d) tiles and make
# a (C, C) decay tile as a column minus a row, so each (1, C) row is also
# stood up as a (C, 1) column: its broadcast down the sublanes masked to the
# diagonal and summed along the lanes (eight vregs; a transpose of a
# half-lane tile is more).

_CHUNKS_A_CELL = 8      # chunks a grid step takes where their number allows
_VMEM = 64 << 20        # of the v5e's 128 MiB


@dataclasses.dataclass(frozen=True)
class _Cell:
    """The static part of a call, equal by value, so that the blocks of a
    model share one trace of it."""
    c: int                      # chunk
    n: int                      # chunks a grid cell takes
    # Pallas' interpreter runs on XLA's CPU backend, which has no bf16
    # product with a transposed operand: there the operands are widened
    # first (exact: a product of two bf16 values fits float32)
    interpret: bool

    def dot(self, a, b, dims=(((1,), (0,)), ((), ()))):
        if self.interpret:
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)

    def dot_nt(self, a, b):
        return self.dot(a, b, _NT)

    def dot_tn(self, a, b):
        return self.dot(a, b, _TN)

    def exact(self, a, b, dims=(((1,), (0,)), ((), ()))):
        """A float32 x float32 product at float32 precision."""
        return lax.dot_general(a, b, dims, precision=_EXACT,
                               preferred_element_type=jnp.float32)

    @property
    def rows(self):
        return _iota((self.c, self.c), 0)

    @property
    def cols(self):
        return _iota((self.c, self.c), 1)

    def column(self, row):
        """(1, C) -> (C, 1)."""
        return jnp.sum(jnp.where(self.rows == self.cols, jnp.broadcast_to(
            row, (self.c, self.c)), 0.0), axis=1, keepdims=True)

    def row(self, column):
        """(C, 1) -> (1, C)."""
        return jnp.sum(jnp.where(self.rows == self.cols, jnp.broadcast_to(
            column, (self.c, self.c)), 0.0), axis=0, keepdims=True)


def _solve(systems, hd):
    """``(I + a)^-1`` of each strictly lower (C, C) float32 tile of
    ``systems`` (one, or two side by side on the 128 lanes of a vreg) in
    VMEM, by substitution on the VPU: exact float32 and no product. Row
    ``i`` of the inverse is ``e_i - sum_{j<i} a_ij T_j``, taken a column
    ``j`` of ``a`` at a time over the eight-row tiles that hold rows past
    ``j`` (row ``j`` is final by then): 63 dependent steps, each a lane
    broadcast of ``a``'s column a tile (one XLU permute a vreg, which is
    what binds: two systems a vreg halve it), a multiply and a subtract
    (over all the tiles at once: a kernel is lowered anew for every program
    that holds it, and an operation a tile made that 5 s a program).
    ``_unit_lower_inverse``'s twelve (C, C) products at float32 precision
    compile to 143 bundles apiece."""
    c, m = hd.c, len(systems)
    a = systems[0] if m == 1 else jnp.concatenate(systems, axis=1)
    lane = _iota((c, m * c), 1)
    inverse = jnp.where(_iota((c, m * c), 0) == lane % c, 1.0, 0.0)
    own = lane[:8] // c * c             # a tile's lanes -> their system's
    for j in range(c - 1):
        top = (j + 1) // 8 * 8          # the tiles above hold no row past j
        # (a gather takes one vreg at a time)
        column = a[top:, j:j + 1] if m == 1 else jnp.concatenate(
            [jnp.take_along_axis(a[r:r + 8], own + j, axis=1)
             for r in range(top, c, 8)], axis=0)
        below = inverse[top:] - column * inverse[j:j + 1]
        inverse = below if top == 0 else jnp.concatenate(
            [inverse[:top], below], axis=0)
    return [inverse[:, i * c:(i + 1) * c] for i in range(m)]


@dataclasses.dataclass
class _System:
    """A chunk's triangular system and its decays, from ``k``, ``gc`` and
    ``beta`` alone: nothing here waits for the state."""
    decay: jax.Array        # Gamma on and below the diagonal (C, C) f32
    kk: jax.Array           # K K^T (C, C) f32
    a: jax.Array            # A
    bc: jax.Array           # beta (C, 1)
    grow: jax.Array         # exp(gc) (C, 1)
    to_end: jax.Array       # exp(gc_C - gc) (C, 1)
    whole: jax.Array        # exp(gc_C) along the lanes (1, d_v)


def _system(k, rows, d_v, hd):
    c = hd.c
    gcr = rows[0:1]
    gcc, bc = hd.column(gcr), hd.column(rows[1:2])
    # position l reads s <= l at decay exp(gc_l - gc_s) <= 1
    decay = jnp.where(hd.rows >= hd.cols,
                      jnp.exp(jnp.minimum(gcc - gcr, 0.0)), 0.0)
    kk = hd.dot_nt(k, k)
    a = jnp.where(hd.rows > hd.cols, kk * decay * bc, 0.0)
    # a (1, 1) value goes along the lanes OR down the sublanes, not both:
    # the column's last rows along the lanes, then the last of them
    whole = jnp.exp(jnp.broadcast_to(gcc[c - 8:], (8, d_v))[7:])
    return _System(decay, kk, a, bc, jnp.exp(gcc),
                   jnp.exp(gcr[:, c - 1:c] - gcc), whole)


@dataclasses.dataclass
class _Chunk:
    """What a chunk makes of its solved system and the state it starts
    from, rebuilt in VMEM; the module docstring's names, each rounded to
    the operands' dtype once."""
    t: jax.Array            # T
    kb: jax.Array           # diag(beta exp gc) K
    vb: jax.Array           # diag(beta) V
    w: jax.Array            # W
    sc: jax.Array           # the state's copy for its read-outs
    vn: jax.Array           # V'
    ke: jax.Array           # K~ = diag(exp(gc_C - gc)) K


def _chunk(sy, t32, k, v, s, hd):
    f32, cd = jnp.float32, k.dtype
    t = t32.astype(cd)
    kf = k.astype(f32)
    kb = (kf * (sy.bc * sy.grow)).astype(cd)
    vb = (v.astype(f32) * sy.bc).astype(cd)
    w = hd.dot(t, kb).astype(cd)
    sc = s.astype(cd)
    vn = (hd.dot(t, vb) - hd.dot(w, sc)).astype(cd)
    return _Chunk(t, kb, vb, w, sc, vn, (kf * sy.to_end).astype(cd))


def _fwd_chunk(q_ref, k_ref, v_ref, rows_ref, o_ref, before_ref, s_ref, sy,
               t32, hd):
    f32, cd = jnp.float32, k_ref.dtype
    q, k = q_ref[...], k_ref[...]
    s = s_ref[...]
    if before_ref is not None:
        before_ref[...] = s
    ch = _chunk(sy, t32, k, v_ref[...], s, hd)
    p = (hd.dot_nt(q, k) * sy.decay).astype(cd)
    qg = (q.astype(f32) * sy.grow).astype(cd)
    o_ref[...] = (hd.dot(p, ch.vn) + hd.dot(qg, ch.sc)).astype(o_ref.dtype)
    s_ref[...] = sy.whole * s + hd.dot_tn(ch.ke, ch.vn)


def _bwd_chunk(q_ref, k_ref, v_ref, rows_ref, before_ref, do_ref, dq_ref,
               dk_ref, dv_ref, drows_ref, ds_ref, sy, t32, hd):
    # the chunk's lines in reverse, ``ds_ref`` the cotangent of the state
    # the chunk ENDS with. A cotangent is rounded to the operands' dtype
    # where it enters a product, as XLA rounds it under the XLA form
    c, f32, cd = hd.c, jnp.float32, k_ref.dtype
    q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
    s, ds_end = before_ref[...], ds_ref[...]
    ch = _chunk(sy, t32, k, v, s, hd)
    seen = hd.rows >= hd.cols
    qk = hd.dot_nt(q, k)
    pf = qk * sy.decay
    p = pf.astype(cd)
    qf, kf, vf = q.astype(f32), k.astype(f32), v.astype(f32)
    qg = (qf * sy.grow).astype(cd)
    ds_c = ds_end.astype(cd)

    # o = P V' + (diag(exp gc) Q) S;  S' = exp(gc_C) S + K~^T V'
    dvn = (hd.dot_tn(p, do) + hd.dot(ch.ke, ds_c)).astype(cd)
    # V' = U - W S
    ds_ref[...] = sy.whole * ds_end + hd.dot_tn(qg, do) \
        - hd.dot_tn(ch.w, dvn)
    dw = (-hd.dot_nt(dvn, ch.sc)).astype(cd)
    dp = jnp.where(seen, hd.dot_nt(do, ch.vn), 0.0)
    dqg = hd.dot_nt(do, ch.sc)
    dke = hd.dot_nt(ch.vn, ds_c)
    # W = T Kb, U = T Vb;  T = (I + A)^-1: dA = -T^T dT T^T, strictly lower
    dt = hd.dot_nt(dw, ch.kb) + hd.dot_nt(dvn, ch.vb)
    dkb = hd.dot_tn(ch.t, dw)
    dvb = hd.dot_tn(ch.t, dvn)
    da = jnp.where(hd.rows > hd.cols, -hd.exact(
        t32, hd.exact(dt, t32, _NT), _TN), 0.0)
    # A = K K^T * Gamma * beta;  P = Q K^T * Gamma
    dkk = (da * sy.decay * sy.bc).astype(cd)
    dqk = (dp * sy.decay).astype(cd)
    dq_ref[...] = (dqg * sy.grow + hd.dot(dqk, k)).astype(dq_ref.dtype)
    dk_ref[...] = (dkb * (sy.bc * sy.grow) + dke * sy.to_end
                   + hd.dot(dkk, k) + hd.dot_tn(dkk, k)
                   + hd.dot_tn(dqk, q)).astype(dk_ref.dtype)
    dv_ref[...] = (dvb * sy.bc).astype(dv_ref.dtype)

    # the scalars: every decay is exp of a difference of gc
    def along(x):
        return jnp.sum(x, axis=1, keepdims=True)

    of_kb = along(dkb * kf)
    to_the_end = along(dke * kf) * sy.to_end
    through = dp * pf + da * sy.a       # d Gamma * Gamma
    dgc = along(dqg * qf) * sy.grow - to_the_end \
        + of_kb * sy.bc * sy.grow + along(through)
    dbeta = along(dvb * vf) + of_kb * sy.grow \
        + along(da * sy.kk * sy.decay)
    dwhole = jnp.sum(to_the_end, axis=0, keepdims=True) \
        + jnp.sum(jnp.sum(sy.whole * ds_end * s, axis=1, keepdims=True),
                  axis=0, keepdims=True)
    last = _iota((1, c), 1) == c - 1
    drows_ref[...] = jnp.concatenate([
        hd.row(dgc) - jnp.sum(through, axis=0, keepdims=True)
        + jnp.where(last, dwhole, 0.0), hd.row(dbeta)], axis=0)


def _views(refs, kinds, j, c):
    """The refs of a grid cell's blocks at its ``j``-th chunk."""
    at = {"tokens": lambda r: r.at[0, 0, pl.ds(pl.multiple_of(j * c, c), c)],
          "chunk": lambda r: r.at[0, 0, j]}
    return [at[kind](r) for kind, r in zip(kinds, refs)]


def _each_chunk(chunk, blocks, kinds, hd, flip):
    """``chunk(*views, system, T)`` for the chunks of a grid cell, first to
    last or with ``flip`` last to first, two a loop step where there are
    two: their triangular systems wait for no state, and two solved side by
    side on a vreg's lanes cost what one does."""
    n = hd.n
    at = (lambda j: n - 1 - j) if flip else (lambda j: j)

    def run(chunks):
        views = [_views(blocks, kinds, j, hd.c) for j in chunks]
        systems = [_system(v[1][...], v[3][...], v[2].shape[-1], hd)
                   for v in views]
        solved = _solve([sy.a for sy in systems], hd)
        for view, sy, t32 in zip(views, systems, solved):
            chunk(*view, sy, t32)

    def pair(i, _):
        run([at(2 * i), at(2 * i + 1)])
        return 0
    lax.fori_loop(0, n // 2, pair, 0)
    if n % 2:
        run([at(n - 1)])


def _fwd_cell(*refs, hd, kinds):
    *blocks, s_ref = refs

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    def chunk(*views):
        *views, sy, t32 = views
        if len(views) == 5:             # no states asked for
            views.append(None)
        _fwd_chunk(*views, s_ref, sy, t32, hd)
    _each_chunk(chunk, blocks, kinds, hd, False)


def _bwd_cell(*refs, hd, kinds):
    *blocks, ds_ref = refs

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    def chunk(*views):
        *views, sy, t32 = views
        _bwd_chunk(*views, ds_ref, sy, t32, hd)
    _each_chunk(chunk, blocks, kinds, hd, True)


def _call(cell, name, hd, ins, outs, flip):
    """One Mosaic call over the grid (batch, head, groups of chunks), the
    groups in order, or last to first with ``flip``. ``ins`` are (kind,
    array) with q, k and v first; ``outs`` (kind, dtype)."""
    bsz, h, length, d_k = ins[0][1].shape
    d_v = ins[2][1].shape[3]
    c, n = hd.c, hd.n
    groups = length // (n * c)
    at = (lambda i: groups - 1 - i) if flip else (lambda i: i)
    spec = {
        "q": pl.BlockSpec((1, 1, n * c, d_k),
                          lambda b, i, j: (b, i, at(j), 0)),
        "v": pl.BlockSpec((1, 1, n * c, d_v),
                          lambda b, i, j: (b, i, at(j), 0)),
        "rows": pl.BlockSpec((1, 1, n, 2, c),
                             lambda b, i, j: (b, i, at(j), 0, 0)),
        "states": pl.BlockSpec((1, 1, n, d_k, d_v),
                               lambda b, i, j: (b, i, at(j), 0, 0))}
    shape = {"q": (bsz, h, length, d_k), "v": (bsz, h, length, d_v),
             "rows": (bsz, h, length // c, 2, c),
             "states": (bsz, h, length // c, d_k, d_v)}
    kinds = [k for k, _ in ins] + [k for k, _ in outs]
    views = tuple("tokens" if k in "qv" else "chunk" for k in kinds)
    return pl.pallas_call(
        functools.partial(cell, hd=hd, kinds=views),
        out_shape=tuple(jax.ShapeDtypeStruct(shape[k], d) for k, d in outs),
        grid=(bsz, h, groups),
        in_specs=[spec[k] for k, _ in ins],
        out_specs=tuple(spec[k] for k, _ in outs),
        scratch_shapes=[pltpu.VMEM((d_k, d_v), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=hd.interpret,
        name=name,
    )(*(t for _, t in ins))


# Each call is a jit of its own, as ``ops/ssd_scan.py``'s: the blocks of a
# model make the same calls at the same shapes, and a jit inside a jit is
# traced and lowered once for all of them.

@functools.partial(jax.jit, static_argnums=(4, 5))
def _fwd_call(q, k, v, rows, hd, states):
    """``o`` (B, H, L, d_v) and, with ``states``, each chunk's start state
    (B, H, nc, d_k, d_v) float32."""
    outs = [("v", v.dtype)] + ([("states", jnp.float32)] if states else [])
    return _call(_fwd_cell, "delta_rule_fwd", hd,
                 [("q", q), ("q", k), ("v", v), ("rows", rows)], outs, False)


@functools.partial(jax.jit, static_argnums=(6,))
def _bwd_call(q, k, v, rows, before, do, hd):
    """d q, d k, d v and d rows of ``_fwd_call``, the chunks last to
    first."""
    return _call(_bwd_cell, "delta_rule_bwd", hd,
                 [("q", q), ("q", k), ("v", v), ("rows", rows),
                  ("states", before), ("v", do)],
                 [("q", q.dtype), ("q", k.dtype), ("v", v.dtype),
                  ("rows", jnp.float32)], True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _chunks(q, k, v, rows, hd):
    """The recurrence from its operands as the kernels take them. Where
    nothing is differentiated no state reaches HBM (under block remat the
    first pass runs ``_chunks_fwd`` too: its states are written and never
    read, since XLA cannot drop one output of a custom call)."""
    return _fwd_call(q, k, v, rows, hd, False)[0]


def _chunks_fwd(q, k, v, rows, hd):
    o, before = _fwd_call(q, k, v, rows, hd, True)
    return o, (q, k, v, rows, before)


@under_scope("delta_rule")
def _chunks_bwd(hd, res, do):
    return tuple(_bwd_call(*res, do, hd))


_chunks.defvjp(_chunks_fwd, _chunks_bwd)


def _delta_kernel(q, k, v, g, beta, c, interpret=None):
    """The kernel form; ``interpret`` (tests on a CPU) runs the kernels in
    Pallas' interpreter."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bsz, length, h, _ = q.shape
    cd, f32 = v.dtype, jnp.float32
    q, k, v, g, beta = _padded(c, length, q, k, v, g, beta)
    nc = q.shape[1] // c
    per = max(n for n in range(1, _CHUNKS_A_CELL + 1) if nc % n == 0)
    # (tokens, heads)-sized, in XLA: the log-decay cumulated from each
    # chunk's start (a product with a 0/1 triangle at full float32
    # precision, as ``ops/ssd_scan.py``'s) over beta, a chunk along lanes
    g, beta = (jnp.moveaxis(t.astype(f32), 1, 2).reshape(bsz, h, nc, c)
               for t in (g, beta))
    upto = (jnp.arange(c)[:, None] <= jnp.arange(c)[None, :]).astype(f32)
    gc = jnp.einsum("bhns,st->bhnt", g, upto, precision=_EXACT)
    heads_out = lambda t: jnp.swapaxes(t.astype(cd), 1, 2)
    o = _chunks(heads_out(q), heads_out(k), heads_out(v),
                jnp.stack([gc, beta], axis=3), _Cell(c, per, bool(interpret)))
    return jnp.swapaxes(o, 1, 2)[:, :length]
