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

so the state at each chunk's start follows from one LINEAR step a chunk,
``S' = M S + add`` with ``M = exp(gc_C) I - K~^T W`` (d_k, d_k) and ``add =
K~^T U``, both made for every chunk at once; ``_chunk_states`` carries it,
one (d_k, d_k) x (d_k, d_v) product a chunk and head (``ops/ssd_scan``'s
carry is the same scan with a scalar in ``M``'s place). ``T`` comes from
``2 log2 C`` products of (C, C) matrices on the MXU where a substitution
goes row by row: the inverses of the diagonal blocks, doubled in width a
level (``_unit_lower_inverse``).

One form, plain ``jax.numpy`` that XLA compiles and autodiff
differentiates (``form="chunked"`` of ``bigdl_delta_rule_total``).
Precision: the products over ``d_k``, ``d_v`` and ``C`` take their
operands in ``q``'s dtype (bf16 under the training policy) and accumulate
in float32; the decays stay in log space until a difference of them is
exponentiated (every exponent is <= 0); ``A``, ``T`` (its products at
``HIGHEST``), ``M`` and the carried state are float32, the carry's product
at ``HIGHEST``; ``T``, ``W``, ``U``, ``V'`` and the state's copy for its
read-outs are rounded to the operands' dtype before their products.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.ops.scopes import under_scope

_EXACT = lax.Precision.HIGHEST


def gated_delta_rule(q, k, v, g, beta, chunk: int = 64):
    """``q``, ``k`` (B, L, H, d_k) and ``v`` (B, L, H, d_v) in one dtype,
    ``g`` (B, L, H) the log-decay (<= 0) and ``beta`` (B, L, H) the write
    strength, float32 -> ``o`` (B, L, H, d_v) in ``v``'s dtype. ``q`` and
    ``k`` as the recurrence reads them (normalised and scaled by the
    caller). L need not be a multiple of ``chunk``: the tail is padded with
    ``g = 0`` and ``beta = 0`` (no decay, no write), which leaves the state
    and every real output as they are."""
    from bigdl_tpu.telemetry import get_registry, instruments
    # trace-time count, as bigdl_ssd_scan_total: the form a compiled
    # program holds (``chunked`` is the only one there is)
    instruments(get_registry()).delta_rule_total.labels(form="chunked").inc()
    with jax.named_scope("delta_rule"):
        return _delta_chunked(q, k, v, g, beta, chunk)


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
    pad = (-length) % c
    if pad:
        q, k, v, g, beta = (jnp.pad(t, [(0, 0), (0, pad)]
                                    + [(0, 0)] * (t.ndim - 2))
                            for t in (q, k, v, g, beta))
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
